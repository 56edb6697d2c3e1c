"""One generator for every traffic mix: reads a mix's data file and draws
its jobs from ``--seed``.

A mix file (``bench/traffic/<mix>.json``) holds parameters only:

- ``"loop": "closed"`` -- one client sends sweeps back to back; a sweep is
  every (network, strategy set, objective) triple of the configuration at
  one area budget, sent together, and the next starts when all resolve.
- ``"loop": "open"`` -- independent clients send single jobs at a fixed
  rate (``rate_per_s``) with exponential gaps; each arrival is one triple
  at one budget.

Budgets come from the grid ``budget_mm2 = [lo, hi, step]`` and never
repeat within a run, so no job key repeats.  Every seed gets the same
amount of work:

- budgets follow a van der Corput sequence over the grid, rotated by the
  seed, so any prefix of sweeps covers the grid evenly;
- open-loop arrivals are one schedule for every seed: a fixed shuffle of
  the exponential quantiles at the mix's rate, and a fixed order of the
  triples (each cycle through all of them shuffled once).  A queue near
  its capacity answers a burst with a backlog, so a schedule drawn from
  each seed would make the latency tail a property of the seed; the seed
  draws the budgets, and with them every job's answer.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


#: the one arrival schedule of every open-loop run
SCHEDULE_SEED = 20260118


@dataclasses.dataclass(frozen=True)
class Arrival:
    """One job to send: when (seconds after the window opens) and what."""

    due_s: float
    triple: tuple            # (network, strategy set, objective)
    budget: float


def _vdc(i: int) -> float:
    """Van der Corput radical inverse of ``i`` in base 2."""
    out, denom = 0.0, 1.0
    while i:
        denom *= 2.0
        out += (i & 1) / denom
        i >>= 1
    return out


def budget_grid(mix: dict) -> np.ndarray:
    lo, hi, step = mix["budget_mm2"]
    n = int(round((hi - lo) / step)) + 1
    return np.round(lo + step * np.arange(n), 6)


def budgets(mix: dict, rng: np.random.Generator, count: int) -> list:
    """``count`` distinct budgets of the mix's grid, evenly spread."""
    grid = budget_grid(mix)
    if count > len(grid):
        raise ValueError(f"{count} budgets asked of a grid of {len(grid)}")
    shift = float(rng.random())
    used = np.zeros(len(grid), bool)
    out = []
    for i in range(count):
        j = int(((_vdc(i + 1) + shift) % 1.0) * len(grid))
        while used[j]:
            j = (j + 1) % len(grid)
        used[j] = True
        out.append(float(grid[j]))
    return out


def triples(config: dict) -> list:
    """Every (network, strategy set, objective) of a configuration."""
    return [(net, sset, obj) for net in config["networks"]
            for sset in config["strategy_sets"]
            for obj in config["objectives"]]


def closed_sweeps(mix: dict, config: dict, seed: int) -> list:
    """The budgets of successive sweeps (more than a window can use)."""
    rng = np.random.default_rng([seed, 1])
    return budgets(mix, rng, mix["max_sweeps"])


def open_arrivals(mix: dict, config: dict, seed: int,
                  seconds: float) -> list:
    """Arrivals due in ``[0, seconds)`` at the mix's fixed rate."""
    rng = np.random.default_rng(SCHEDULE_SEED)
    rate = float(mix["rate_per_s"])
    n = int(math.ceil(rate * seconds)) + 1
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = gaps[rng.permutation(n)]
    due = np.cumsum(gaps)
    due = due[due < seconds]
    all_triples = triples(config)
    order = []
    while len(order) < len(due):
        order.extend(all_triples[i]
                     for i in rng.permutation(len(all_triples)))
    spent = budgets(mix, np.random.default_rng([seed, 2]), len(due))
    return [Arrival(float(t), order[i], spent[i])
            for i, t in enumerate(due)]
