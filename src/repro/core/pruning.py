"""Hardware design-space enumeration and pruning (paper Sec. III-D).

Constraints applied:
  1. power-of-two SCR / IS_SIZE / OS_SIZE (address-decoding alignment);
  2. internal bandwidth (aggregate ICW, WUW) >= external bus BW;
  3. area(cfg) <= budget.

Only constraint 3 depends on the job's budget: the enumerated points, their
areas, their bandwidth test and their cost-model rows are built once per
(space, macro, tech, bw) into a cached :class:`SpaceTable`, and each job
applies one budget mask.

The pruned fraction is reported by benchmarks/fig9_runtime.py (paper: >35 %).
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import threading

import numpy as np

from repro import obs
from repro.core.calibration import TechConstants, resolve_tech
from repro.core.macro import MacroSpec

MR_CHOICES = (1, 2, 3, 4, 6, 8)
MC_CHOICES = (1, 2, 3, 4, 6, 8)
SCR_CHOICES = (1, 2, 4, 8, 16, 32, 64)
IS_KB_CHOICES = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)
OS_KB_CHOICES = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)

_M_TABLE = obs.registry().counter(
    "cim_engine_prune_table_total",
    "Design-space table lookups of pruning by outcome", ("outcome",))
_M_TABLE.inc(0, outcome="hit")  # eager: the family shows even when idle
_M_TABLE.inc(0, outcome="miss")


@dataclasses.dataclass(frozen=True)
class DesignSpace:
    mr: tuple[int, ...] = MR_CHOICES
    mc: tuple[int, ...] = MC_CHOICES
    scr: tuple[int, ...] = SCR_CHOICES
    is_kb: tuple[int, ...] = IS_KB_CHOICES
    os_kb: tuple[int, ...] = OS_KB_CHOICES

    def __post_init__(self) -> None:
        # tuples, so a space hashes by value (the pruning table's key)
        for name in ("mr", "mc", "scr", "is_kb", "os_kb"):
            object.__setattr__(self, name, tuple(getattr(self, name)))

    def axes(self) -> tuple[tuple[int, ...], ...]:
        return (self.mr, self.mc, self.scr, self.is_kb, self.os_kb)

    @property
    def size(self) -> int:
        return int(np.prod([len(a) for a in self.axes()]))

    def fix(self, **fixed: int) -> "DesignSpace":
        """Pin axes to single values (Table II: 'other parameters fixed')."""
        kw = {}
        for name in ("mr", "mc", "scr", "is_kb", "os_kb"):
            kw[name] = (fixed[name],) if name in fixed else getattr(self, name)
        return DesignSpace(**kw)


def enumerate_space(space: DesignSpace) -> np.ndarray:
    """All raw candidate tuples as an int array [C, 5]."""
    return np.array(
        list(itertools.product(*space.axes())), dtype=np.int64
    )


@dataclasses.dataclass(frozen=True, eq=False)
class SpaceTable:
    """The budget-independent part of pruning one design space.

    Every point in ``itertools.product`` order, its area, its bandwidth
    test and its cost-model row; only ``area <= budget`` depends on the
    job.  The arrays are read-only: callers get copies of the kept rows
    (``compress``, which copies a 2-D selection several times faster than
    a boolean index)."""

    points: np.ndarray     # [C, 5] int64 (MR, MC, SCR, IS, OS)
    area: np.ndarray       # [C] float64 mm^2
    bw_ok: np.ndarray      # [C] bool
    n_bw_ok: int
    rows: np.ndarray       # [C, 6] float64, ``candidates_with_bw(points)``

    def keep(self, area_budget_mm2: float) -> np.ndarray:
        """The job's mask: bandwidth-feasible and within its budget."""
        return self.bw_ok & (self.area <= area_budget_mm2)

    def stats(self, keep: np.ndarray) -> dict:
        """The pruning counts of one mask, as ``prune_space`` reports them."""
        raw = len(self.points)
        kept = keep.sum()
        return {
            "raw": raw,
            "kept": int(kept),
            "bandwidth_pruned": raw - self.n_bw_ok,
            "area_pruned": self.n_bw_ok - int(kept),
            "pruned_fraction": 1.0 - kept / max(1, raw),
        }


def _build_table(space: DesignSpace, macro: MacroSpec, tech: TechConstants,
                 bw: int) -> SpaceTable:
    raw = enumerate_space(space)
    mr, mc, scr, is_kb, os_kb = (raw[:, i].astype(np.float64)
                                 for i in range(5))
    bw_ok = (macro.icw * mr >= bw) & (macro.wuw * mr * mc >= bw)
    cells = macro.al * macro.pc * scr * macro.dw_w * tech.a_cell_um2_bit
    cus = macro.al * macro.pc * tech.a_cu_um2
    macro_area = (cells + cus) * 1e-6 + tech.a_macro_fixed_mm2
    sram = lambda kb: kb * 8.0 / 1024.0 * tech.a_sram_mm2_per_mb \
        + tech.a_sram_fixed_mm2
    area = mr * mc * macro_area + sram(is_kb) + sram(os_kb) + tech.a_fixed_mm2
    table = SpaceTable(points=raw, area=area, bw_ok=bw_ok,
                       n_bw_ok=int(bw_ok.sum()),
                       rows=candidates_with_bw(raw, bw))
    for a in (table.points, table.area, table.bw_ok, table.rows):
        a.setflags(write=False)
    return table


#: tables by (space, macro, tech, bw), least recently used first; a
#: full-space table is about 3 MB
_TABLES: collections.OrderedDict = collections.OrderedDict()
_TABLES_MAX = 32
_TABLES_LOCK = threading.Lock()


def space_table(space: DesignSpace, macro: MacroSpec, bw: int = 256,
                tech: TechConstants | None = None) -> SpaceTable:
    """The cached :class:`SpaceTable` of one (space, macro, tech, bw);
    every part of the key is frozen, so equal values share one entry."""
    key = (space, macro, resolve_tech(tech), bw)
    with _TABLES_LOCK:
        table = _TABLES.get(key)
        if table is not None:
            _TABLES.move_to_end(key)
    if table is not None:
        _M_TABLE.inc(outcome="hit")
        return table
    table = _build_table(*key)
    with _TABLES_LOCK:
        _TABLES[key] = table
        while len(_TABLES) > _TABLES_MAX:
            _TABLES.popitem(last=False)
    _M_TABLE.inc(outcome="miss")
    return table


def clear_space_tables() -> None:
    """Drop every cached table (the next lookup of each key builds it)."""
    with _TABLES_LOCK:
        _TABLES.clear()


def prune_space(
    space: DesignSpace,
    macro: MacroSpec,
    area_budget_mm2: float,
    bw: int = 256,
    tech: TechConstants | None = None,
) -> tuple[np.ndarray, dict]:
    """Returns ([C_valid, 5] candidates, stats) after bandwidth+area pruning.

    Vectorized (the same closed-form area/bandwidth rules as template.py --
    pinned against the scalar path in tests/test_explorer.py)."""
    table = space_table(space, macro, bw, tech)
    keep = table.keep(area_budget_mm2)
    return table.points.compress(keep, axis=0), table.stats(keep)


def prune_rows(
    space: DesignSpace,
    macro: MacroSpec,
    area_budget_mm2: float,
    bw: int = 256,
    tech: TechConstants | None = None,
) -> tuple[np.ndarray, dict]:
    """:func:`prune_space` as ``[C_valid, 6]`` cost-model rows, equal to
    ``candidates_with_bw`` of its candidates, with the same stats."""
    table = space_table(space, macro, bw, tech)
    keep = table.keep(area_budget_mm2)
    return table.rows.compress(keep, axis=0), table.stats(keep)


def candidates_with_bw(cands: np.ndarray, bw: int) -> np.ndarray:
    """Append the bus-bandwidth column -> cfg rows for the jnp cost model."""
    col = np.full((len(cands), 1), bw, dtype=np.int64)
    return np.concatenate([cands, col], axis=1).astype(np.float64)
