"""queue.submit_ms_per_job: milliseconds per job resolved in a
closed-loop window spent in ``JobQueue.submit``/``submit_many`` on the
client's thread (key hashing, store lookup, enqueue; the queue's
``submit`` phase)."""
from _phases import QUEUE, per_job


def read(run):
    return per_job(run, QUEUE, ("submit",), scale=1e3)
