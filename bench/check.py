"""The comparison that decides ``correct``.

Every job due in the window is compared with the plain reference
(``bench/reference.py``, float64), as the future delivered it after the
queue and the store.  The numbers compared, each against its limit in
``bench/checks/<workload>.json``:

- ``missing``: jobs due in the window with no result a minute past the
  close, or that failed (exact: limit 0);
- ``store_mismatch``: results whose stored copy differs from what the
  future delivered (exact: limit 0);
- ``metric_err``: the largest relative error of the delivered cycles, pJ,
  mm^2, TOPS/W and GOPS against the reference's at the delivered config;
- ``strategy_excess``: the largest relative excess of a delivered
  per-operator strategy's score over the best the job's set allows at the
  delivered config (0 when each operator got its best strategy);
- ``regret`` (exhaustive search): the reference objective of the delivered
  config over the reference's pruned-space optimum, minus one;
- ``budget_excess`` (stochastic search): how far the delivered design's
  area lies over the budget, as a share of it; the configuration states
  the program's slack (``area_slack``).
"""
from __future__ import annotations

import dataclasses
import math

METRIC_KEYS = ("latency_cycles", "energy_pj", "area_mm2", "tops_w", "gops")


@dataclasses.dataclass
class Answer:
    """What a job's answer says, from the program or from the control."""

    cfg: tuple               # (mr, mc, scr, is_kb, os_kb)
    bw: int
    metrics: dict
    per_op: dict             # op name -> strategy name


def from_result(r) -> Answer:
    return Answer(tuple(int(v) for v in r.config.as_tuple()),
                  int(r.config.bw), dict(r.metrics), dict(r.per_op_strategy))


def _rel(a: float, b: float) -> float:
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / abs(b) if b else abs(a - b)


def judge(ref, triple, budget, ans: Answer, method: str) -> dict:
    """The compared numbers of one delivered answer, plus ``optimal``
    (the delivered objective equals the pruned-space optimum)."""
    net, sset, obj = triple
    inf = math.inf
    ra = ref.answer(net, sset, obj, budget, ans.cfg)
    _, opt = ref.optimum(net, sset, obj, budget)
    out = {"metric_err": inf, "strategy_excess": inf,
           "optimal": ra.feasible and ra.value <= opt}
    if method == "exhaustive":
        out["regret"] = ra.value / opt - 1.0 if ra.feasible else inf
    else:
        out["budget_excess"] = max(0.0, ref.area(ans.cfg) / budget - 1.0)
    if not ra.metrics or ans.bw != int(ref.bw):
        return out
    out["metric_err"] = max(_rel(ans.metrics.get(k, inf), ra.metrics[k])
                            for k in METRIC_KEYS)
    if set(ans.per_op) != set(ra.op_scores):
        return out
    excess = 0.0
    for name, strategy in ans.per_op.items():
        scores = ra.op_scores[name]
        if strategy not in scores:
            return out
        excess = max(excess, _rel(scores[strategy], min(scores.values())))
    out["strategy_excess"] = excess
    return out


def compare(items, ref, method: str) -> tuple[dict, list]:
    """``items``: ``(triple, budget, Answer | None)`` for every job due in
    the window.  Returns the cell's numbers (worst over jobs) and the
    per-job ``optimal`` flags (``False`` for a missing answer)."""
    numbers = {"missing": 0, "metric_err": 0.0, "strategy_excess": 0.0}
    numbers["regret" if method == "exhaustive" else "budget_excess"] = 0.0
    optimal = []
    for triple, budget, ans in items:
        if ans is None:
            numbers["missing"] += 1
            optimal.append(False)
            continue
        got = judge(ref, triple, budget, ans, method)
        optimal.append(bool(got.pop("optimal")))
        for k, v in got.items():
            numbers[k] = max(numbers[k], v)
    return numbers, optimal


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and ``{name: {"value", "limit"}}`` for every number;
    a number without a limit, or not finite, fails."""
    table, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        good = (limit is not None and isinstance(value, (int, float))
                and math.isfinite(value) and value <= limit)
        ok = ok and good
        table[name] = {"value": value if math.isfinite(value) else str(value),
                       "limit": limit}
    return ok, table
