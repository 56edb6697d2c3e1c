"""queue.jobs_per_dispatch: jobs resolved in the window per engine call
the queue issued (``cim_queue_dispatches_total``)."""
from _common import delta, resolved


def read(run):
    n = delta(run, "cim_queue_dispatches_total")
    if n <= 0:
        return None
    return len(resolved(run)) / n
