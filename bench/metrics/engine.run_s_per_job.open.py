"""engine.run_s_per_job.open: seconds inside ``ExplorationEngine.run`` per
job resolved in an open-loop window (``cim_engine_run_seconds``)."""
from _common import delta, resolved


def read(run):
    jobs = len(resolved(run))
    if run.mix["loop"] != "open" or jobs == 0:
        return None
    return delta(run, "cim_engine_run_seconds_sum") / jobs
