"""engine.prepare_s_per_job: seconds per job resolved in a closed-loop
window spent preparing jobs (merged operators, axis matrix): the engine's
``prepare`` phase inside ``ExplorationEngine.run`` plus the ``bucket``
phase the queue's grouping runs (``cim_engine_phase_seconds``)."""
from _phases import ENGINE, per_job


def read(run):
    return per_job(run, ENGINE, ("prepare", "bucket"))
