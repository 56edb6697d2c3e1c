"""engine.executable_s_per_job: seconds per job resolved in a closed-loop
window spent in the cost-evaluation executables, from each call until its
result is on the host (the engine's ``executable`` phase: each
``[J, 4096, 6]`` block of the exhaustive sweep, each search dispatch)."""
from _phases import ENGINE, per_job


def read(run):
    return per_job(run, ENGINE, ("executable",))
