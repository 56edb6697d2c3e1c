"""queue.wait_ms_mean: mean submit-to-dispatch wait (ms) of the window's
queue entries, from the program's ``cim_queue_wait_seconds`` histogram."""
from _common import delta


def read(run):
    n = delta(run, "cim_queue_wait_seconds_count")
    if n <= 0:
        return None
    return 1e3 * delta(run, "cim_queue_wait_seconds_sum") / n
