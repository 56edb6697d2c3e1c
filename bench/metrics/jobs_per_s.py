"""jobs_per_s: jobs resolved in a closed-loop window over its length (the
window closes when the sweep running at ``--seconds`` resolves)."""
from _common import resolved


def read(run):
    if run.mix["loop"] != "closed" or run.window_s <= 0:
        return None
    return len(resolved(run)) / run.window_s
