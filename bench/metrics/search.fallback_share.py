"""search.fallback_share: share (%) of the window's stochastic-search
results that carry the pruned-space statistics (``kept``) only the
engine's snap-verify fallback to an exhaustive sweep adds."""
from _common import resolved


def read(run):
    got = resolved(run)
    if run.method == "exhaustive" or not got:
        return None
    return 100.0 * sum("kept" in r.search for r in got) / len(got)
