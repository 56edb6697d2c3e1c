"""queue.resolve_ms_per_job: milliseconds per job resolved in a
closed-loop window spent resolving dispatched groups on the worker's
thread (store writes and their sidecars, in-flight map, futures; the
queue's ``resolve`` phase)."""
from _phases import QUEUE, per_job


def read(run):
    return per_job(run, QUEUE, ("resolve",), scale=1e3)
