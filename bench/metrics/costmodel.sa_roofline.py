"""costmodel.sa_roofline: roofline share (%) of the annealing
cost-evaluation executable in a traced window: the least time for the
chains' work (``bench/work.py``; plus the pruned-space sweeps of jobs that
fell back to one) over the device time of the search modules."""
import peaks
import reduce
import work
from _common import n_strategies, resolved

MODULE = "one_job"


def read(run):
    if run.trace is None or run.method != "sa":
        return None
    s = run.config["settings"]
    flops = nbytes = 0.0
    for r in resolved(run):
        n = n_strategies(run.config, r.strategy_set)
        f, b = work.sa_job(s["n_chains"], s["n_steps"], r.search["merged_ops"],
                           n)
        if "kept" in r.search:
            f2, b2 = work.exhaustive_job(r.search["kept"],
                                         r.search["merged_ops"], n)
            f, b = f + f2, b + b2
        flops, nbytes = flops + f, nbytes + b
    seconds = reduce.module_seconds(run.trace["modules"],
                                    lambda name: MODULE in name)
    return reduce.roofline_pct(flops, nbytes, seconds,
                               peaks.peaks(run.device_kind))
