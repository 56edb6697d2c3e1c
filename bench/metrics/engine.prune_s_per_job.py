"""engine.prune_s_per_job: seconds per job resolved in a closed-loop
window spent pruning the design space on the host (``prune_space`` and
``candidates_with_bw``, the engine's ``prune`` phase)."""
from _phases import ENGINE, per_job


def read(run):
    return per_job(run, ENGINE, ("prune",))
