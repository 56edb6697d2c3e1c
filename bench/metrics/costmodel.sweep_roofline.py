"""costmodel.sweep_roofline: roofline share (%) of the exhaustive
cost-evaluation executable in a traced window: the least time the chip
could take for the sweeps' work (``bench/work.py``, counted from the
jobs' pruned candidates, merged operators and admitted strategies) over
the device time of the executable's modules in the trace."""
import peaks
import reduce
import work
from _common import n_strategies, resolved

MODULE = "one_job"


def read(run):
    if run.trace is None or run.method != "exhaustive":
        return None
    flops = nbytes = 0.0
    for r in resolved(run):
        f, b = work.exhaustive_job(r.search["kept"], r.search["merged_ops"],
                                   n_strategies(run.config, r.strategy_set))
        flops, nbytes = flops + f, nbytes + b
    seconds = reduce.module_seconds(run.trace["modules"],
                                    lambda name: MODULE in name)
    return reduce.roofline_pct(flops, nbytes, seconds,
                               peaks.peaks(run.device_kind))
