"""engine.finish_s_per_job: seconds per job resolved in a closed-loop
window spent assembling results (the engine's ``finish`` phase: the
winner's pick and the eager epilogue ``_finish``)."""
from _phases import ENGINE, per_job


def read(run):
    return per_job(run, ENGINE, ("finish",))
