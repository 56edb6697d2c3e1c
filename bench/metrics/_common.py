"""Helpers shared by the metric readers (not a metric itself)."""
from __future__ import annotations

import math


def resolved(run) -> list:
    """The window's results that arrived."""
    return [r for r in run.results if r is not None]


def delta(run, key: str) -> float:
    return run.reg1.get(key, 0.0) - run.reg0.get(key, 0.0)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (a missing value counts as infinite)."""
    xs = sorted(values)
    if not xs:
        return math.nan
    return xs[max(0, math.ceil(q / 100.0 * len(xs)) - 1)]


def n_strategies(config: dict, strategy_set: str) -> int:
    return len(config["strategy_sets"][strategy_set])
