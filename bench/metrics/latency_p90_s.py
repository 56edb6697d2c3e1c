"""latency_p90_s: 90th percentile of due-to-resolve seconds over every
job due in an open-loop window; a job that failed or never came counts as
infinitely late."""
import math

from _common import percentile


def read(run):
    if run.mix["loop"] != "open":
        return None
    lat = [(r.resolved - r.due) if r.resolved is not None and res is not None
           else math.inf for r, res in zip(run.records, run.results)]
    p = percentile(lat, 90.0)
    return p if math.isfinite(p) else None
