"""loadgen.late_ms_p90: 90th percentile (ms) of how late the load
generator sent each open-loop job after its due time."""
from _common import percentile


def read(run):
    if run.mix["loop"] != "open" or not run.records:
        return None
    return 1e3 * percentile([r.sent - r.due for r in run.records], 90.0)
