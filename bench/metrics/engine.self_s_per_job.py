"""engine.self_s_per_job: seconds per job resolved in a closed-loop
window inside ``ExplorationEngine.run`` but in none of its phases
(``cim_engine_run_seconds`` less the ``prepare``, ``prune``,
``executable`` and ``finish`` phases): key checks, grouping, candidate
blocks, result plumbing."""
from _common import delta
from _phases import ENGINE, RUN, RUN_PHASES, closed_jobs, phase_seconds


def read(run):
    jobs = closed_jobs(run)
    phases = phase_seconds(run, ENGINE, RUN_PHASES)
    if jobs is None or phases is None:
        return None
    return (delta(run, RUN + "_sum") - phases) / jobs
