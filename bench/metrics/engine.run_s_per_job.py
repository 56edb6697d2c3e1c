"""engine.run_s_per_job: seconds inside ``ExplorationEngine.run`` per job
resolved in a closed-loop window (``cim_engine_run_seconds``)."""
from _common import delta, resolved


def read(run):
    jobs = len(resolved(run))
    if run.mix["loop"] != "closed" or jobs == 0:
        return None
    return delta(run, "cim_engine_run_seconds_sum") / jobs
