"""Helpers of the readers of the program's phase histograms and trace
counter (not a metric itself).  A program without the family (one older
than the phase spans) has no key for it, and the readers then give
``None``."""
from __future__ import annotations

from _common import delta, resolved

ENGINE = "cim_engine_phase_seconds"
QUEUE = "cim_queue_phase_seconds"
RUN = "cim_engine_run_seconds"
#: the engine phases inside ``ExplorationEngine.run``; they never nest
RUN_PHASES = ("prepare", "prune", "executable", "finish")


def phase_key(family: str, phase: str) -> str:
    """The registry snapshot's key of one phase's summed seconds."""
    return f'{family}_sum{{phase="{phase}"}}'


def closed_jobs(run) -> int | None:
    """Jobs resolved in a closed-loop window; ``None`` for other windows
    or none resolved."""
    jobs = len(resolved(run))
    if run.mix["loop"] != "closed" or jobs == 0:
        return None
    return jobs


def phase_seconds(run, family: str, phases) -> float | None:
    """Seconds the window spent in ``phases`` of ``family``; ``None``
    when the program records no such phase."""
    keys = [phase_key(family, p) for p in phases]
    if any(k not in run.reg1 for k in keys):
        return None
    return sum(delta(run, k) for k in keys)


def per_job(run, family: str, phases, scale: float = 1.0) -> float | None:
    """``phase_seconds`` per job resolved in a closed-loop window."""
    jobs = closed_jobs(run)
    secs = phase_seconds(run, family, phases)
    if jobs is None or secs is None:
        return None
    return scale * secs / jobs
