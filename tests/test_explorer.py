"""Pruning + SA + exhaustive co-exploration tests."""
import dataclasses
import itertools
import json

import numpy as np
import pytest

from repro.core import (
    AcceleratorConfig,
    DesignSpace,
    SASettings,
    co_explore,
    evaluate_config,
    get_macro,
    prune_space,
)
from repro.core.calibration import DEFAULT_TECH, CorrectionFactors, TechConstants
from repro.core.ir import bert_large_workload
from repro.core.macro import TPDCIM_MACRO, MacroSpec
from repro.core.pruning import (
    candidates_with_bw,
    clear_space_tables,
    prune_rows,
    space_table,
)

SMALL = DesignSpace(mr=(1, 2, 3), mc=(1, 2), scr=(1, 4, 16),
                    is_kb=(2, 16, 128), os_kb=(2, 16, 64))


def test_prune_space_counts():
    cands, stats = prune_space(SMALL, get_macro("vanilla-dcim"),
                               area_budget_mm2=3.0)
    assert stats["raw"] == SMALL.size == 3 * 2 * 3 * 3 * 3
    assert stats["kept"] == len(cands)
    assert stats["kept"] + stats["bandwidth_pruned"] + \
        stats["area_pruned"] == stats["raw"]
    assert stats["pruned_fraction"] > 0.0
    # every surviving candidate respects the budget + bandwidth rule
    from repro.core.template import accelerator_area_mm2, bandwidth_ok
    for row in cands:
        cfg = AcceleratorConfig(*[int(x) for x in row])
        assert accelerator_area_mm2(cfg, get_macro("vanilla-dcim")) <= 3.0
        assert bandwidth_ok(cfg, get_macro("vanilla-dcim"))


def _reference_prune(space, macro, budget, bw, tech):
    """The uncached pruning the table replaces: ``itertools.product`` and
    the closed-form area and bandwidth rules, evaluated per call."""
    raw = np.array(list(itertools.product(*space.axes())), dtype=np.int64)
    mr, mc, scr, is_kb, os_kb = (raw[:, i].astype(np.float64)
                                 for i in range(5))
    bw_ok = (macro.icw * mr >= bw) & (macro.wuw * mr * mc >= bw)
    cells = macro.al * macro.pc * scr * macro.dw_w * tech.a_cell_um2_bit
    cus = macro.al * macro.pc * tech.a_cu_um2
    macro_area = (cells + cus) * 1e-6 + tech.a_macro_fixed_mm2
    sram = lambda kb: kb * 8.0 / 1024.0 * tech.a_sram_mm2_per_mb \
        + tech.a_sram_fixed_mm2
    area = mr * mc * macro_area + sram(is_kb) + sram(os_kb) + tech.a_fixed_mm2
    area_ok = area <= budget
    keep = bw_ok & area_ok
    stats = {
        "raw": len(raw),
        "kept": int(keep.sum()),
        "bandwidth_pruned": int((~bw_ok).sum()),
        "area_pruned": int((bw_ok & ~area_ok).sum()),
        "pruned_fraction": 1.0 - keep.sum() / max(1, len(raw)),
    }
    rows = np.concatenate(
        [raw[keep], np.full((int(keep.sum()), 1), bw, dtype=np.int64)],
        axis=1).astype(np.float64)
    return raw[keep], rows, stats, area[bw_ok]


_CORRECTED = DEFAULT_TECH.with_corrections(
    CorrectionFactors(compute=1.3, memory=0.8, update=1.1, leakage=0.9))


@pytest.mark.parametrize("tech", [DEFAULT_TECH, _CORRECTED],
                         ids=["default-tech", "corrected-tech"])
@pytest.mark.parametrize("bw", [256, 1024])
@pytest.mark.parametrize("macro", ["vanilla-dcim", "tpdcim"])
@pytest.mark.parametrize("space", ["small", "full", "fixed"])
def test_cached_pruning_equals_the_uncached_reference(space, macro, bw,
                                                      tech):
    """The cached table's ``prune_space`` and the engine's ``prune_rows``
    equal today's per-call pruning, array for array and stat for stat,
    at budgets from 2 to 9 mm^2 and at budgets equal to a point's area."""
    space = {"small": SMALL, "full": DesignSpace(),
             "fixed": DesignSpace().fix(mr=2, scr=16)}[space]
    macro = {"vanilla-dcim": get_macro("vanilla-dcim"),
             "tpdcim": TPDCIM_MACRO}[macro]
    clear_space_tables()
    *_, areas = _reference_prune(space, macro, 0.0, bw, tech)
    # (none where the bus outruns every point's internal bandwidth)
    on_a_point = [float(a) for a in np.quantile(
        np.unique(areas), [0.1, 0.5, 0.9], method="nearest")] \
        if len(areas) else []
    below = [float(np.nextafter(a, -np.inf)) for a in on_a_point]
    kept = {}
    for budget in (2.0, 3.0, 4.25, 5.5, 7.0, 9.0, *on_a_point, *below):
        cands, rows, stats, _ = _reference_prune(space, macro, budget, bw,
                                                 tech)
        got_c, got_stats = prune_space(space, macro, budget, bw, tech)
        got_r, row_stats = prune_rows(space, macro, budget, bw, tech)
        assert np.array_equal(got_c, cands) and got_c.dtype == cands.dtype
        assert np.array_equal(got_r, rows) and got_r.dtype == rows.dtype
        assert got_stats == row_stats == stats
        assert [type(v) for v in got_stats.values()] == \
            [type(v) for v in stats.values()]
        assert np.array_equal(candidates_with_bw(got_c, bw), got_r)
        kept[budget] = got_stats["kept"]
    # the comparison is inclusive: a budget equal to a point's area keeps
    # it, one ulp less does not
    for at, under in zip(on_a_point, below):
        assert kept[at] > kept[under]


def test_pruning_table_is_read_only_and_callers_get_copies():
    macro = get_macro("vanilla-dcim")
    clear_space_tables()
    table = space_table(SMALL, macro, 256)
    for arr in (table.points, table.area, table.bw_ok, table.rows):
        assert not arr.flags.writeable
        with pytest.raises(ValueError):
            arr[0] = 0
    cands, stats = prune_space(SMALL, macro, 3.0)
    rows, _ = prune_rows(SMALL, macro, 3.0)
    want_c, want_r, want_stats, _ = _reference_prune(
        SMALL, macro, 3.0, 256, DEFAULT_TECH)
    cands[:] = -1
    rows[:] = -1.0
    stats["kept"] = -1
    again_c, again_stats = prune_space(SMALL, macro, 3.0)
    again_r, _ = prune_rows(SMALL, macro, 3.0)
    assert np.array_equal(again_c, want_c)
    assert np.array_equal(again_r, want_r)
    assert again_stats == want_stats


def test_equal_keys_built_separately_share_one_table():
    """A macro, tech and space rebuilt from JSON (the wire's form, with
    lists for the space's axes) hit the table the originals built."""
    from repro.core.pruning import _M_TABLE

    def counts():
        return (_M_TABLE.value(outcome="hit"),
                _M_TABLE.value(outcome="miss"))

    macro = get_macro("vanilla-dcim")
    wire = json.loads(json.dumps({
        "macro": dataclasses.asdict(macro),
        "tech": dataclasses.asdict(DEFAULT_TECH),
        "space": dataclasses.asdict(SMALL)}))
    twin_macro = MacroSpec(**wire["macro"])
    twin_tech = TechConstants(**wire["tech"])
    twin_space = DesignSpace(**wire["space"])
    assert twin_macro is not macro and twin_space is not SMALL
    clear_space_tables()
    hit0, miss0 = counts()
    first = prune_space(SMALL, macro, 3.0, 256, DEFAULT_TECH)
    assert counts() == (hit0, miss0 + 1)
    twin = prune_space(twin_space, twin_macro, 3.0, 256, twin_tech)
    assert counts() == (hit0 + 1, miss0 + 1)
    assert space_table(twin_space, twin_macro, 256, twin_tech) is \
        space_table(SMALL, macro, 256, None)
    assert np.array_equal(first[0], twin[0]) and first[1] == twin[1]


@pytest.mark.parametrize("part", ["space", "macro", "tech", "bw"])
def test_each_part_of_the_key_selects_its_own_table(part):
    """A key that differs in one part misses and answers for itself, not
    from the table of the key it resembles."""
    from repro.core.pruning import _M_TABLE
    base = {"space": SMALL, "macro": TPDCIM_MACRO, "tech": DEFAULT_TECH,
            "bw": 256}
    other = dict(base, **{
        "space": {"space": SMALL.fix(mc=2)},
        "macro": {"macro": get_macro("vanilla-dcim")},
        "tech": {"tech": dataclasses.replace(DEFAULT_TECH,
                                             a_sram_fixed_mm2=0.05)},
        "bw": {"bw": 1024}}[part])
    clear_space_tables()
    for key in (base, other):
        miss0 = _M_TABLE.value(outcome="miss")
        got, stats = prune_rows(key["space"], key["macro"], 2.5, key["bw"],
                                key["tech"])
        assert _M_TABLE.value(outcome="miss") == miss0 + 1
        _, rows, want, _ = _reference_prune(key["space"], key["macro"], 2.5,
                                            key["bw"], key["tech"])
        assert np.array_equal(got, rows) and stats == want


def test_fixed_axes():
    s = SMALL.fix(mr=2, scr=16)
    assert s.mr == (2,) and s.scr == (16,)
    assert s.mc == SMALL.mc


def test_sa_matches_exhaustive_on_small_space():
    wl = bert_large_workload()
    kw = dict(macro=TPDCIM_MACRO, workload=wl, area_budget_mm2=2.23,
              objective="ee", space=SMALL)
    ex = co_explore(method="exhaustive", **kw)
    sa = co_explore(method="sa",
                    sa_settings=SASettings(n_chains=24, n_steps=120, seed=1),
                    **kw)
    # SA must reach within 1% of the exhaustive optimum
    assert sa.metrics["energy_pj"] <= ex.metrics["energy_pj"] * 1.01
    assert ex.config.scr >= 1


def test_objectives_differ():
    wl = bert_large_workload()
    ee = co_explore(TPDCIM_MACRO, wl, 2.23, objective="ee",
                    method="exhaustive", space=SMALL)
    th = co_explore(TPDCIM_MACRO, wl, 2.23, objective="th",
                    method="exhaustive", space=SMALL)
    assert th.metrics["gops"] >= ee.metrics["gops"] * 0.999
    assert ee.metrics["tops_w"] >= th.metrics["tops_w"] * 0.999


def test_st_dominates_so():
    """CIM-Tuner's scheduling+tiling space contains [19]'s spatial-only
    space, so the per-config optimum can only improve (Fig. 7 mechanism)."""
    wl = bert_large_workload()
    cfg = AcceleratorConfig(2, 2, 8, 16, 16)
    st_m = evaluate_config(TPDCIM_MACRO, cfg, wl, strategy_set="st")
    so_m = evaluate_config(TPDCIM_MACRO, cfg, wl, strategy_set="so")
    assert st_m["energy_pj"] <= so_m["energy_pj"] * (1 + 1e-9)
    assert st_m["latency_cycles"] <= so_m["latency_cycles"] * (1 + 1e-9)


def test_budget_respected():
    wl = bert_large_workload()
    res = co_explore(TPDCIM_MACRO, wl, 2.0, method="exhaustive", space=SMALL)
    assert res.metrics["area_mm2"] <= 2.0 + 1e-6


def test_per_op_strategies_reported():
    wl = bert_large_workload()
    res = co_explore(TPDCIM_MACRO, wl, 2.23, method="exhaustive", space=SMALL)
    assert len(res.per_op_strategy) == len(wl.merged().ops)
    for v in res.per_op_strategy.values():
        assert v.count("-") == 2


def test_macro_library_co_exploration():
    """Outer macro-family selection on top of the paper's co-exploration."""
    from repro.core import co_explore_macros, get_macro
    wl = bert_large_workload()
    macros = [get_macro("vanilla-dcim"), get_macro("lcc-cim")]
    best, results = co_explore_macros(
        macros, wl, 3.0, objective="ee", method="exhaustive", space=SMALL)
    assert len(results) == 2
    assert best.metrics["tops_w"] == max(r.metrics["tops_w"] for r in results)
    assert best.metrics["area_mm2"] <= 3.0 + 1e-6


def test_pareto_frontier_monotone_and_contains_extremes():
    from repro.core.explorer import pareto_explore
    from repro.core import get_macro
    wl = bert_large_workload()
    macro = get_macro("vanilla-dcim")
    fr = pareto_explore(macro, wl, 5.0, space=SMALL)
    assert len(fr) >= 1
    gops = [p["gops"] for p in fr]
    ee = [p["tops_w"] for p in fr]
    assert all(a >= b for a, b in zip(gops, gops[1:]))   # gops decreasing
    assert all(a <= b for a, b in zip(ee, ee[1:]))       # ee increasing
    # endpoints at least as good as single-objective exhaustive optima
    ee_opt = co_explore(macro, wl, 5.0, objective="ee", method="exhaustive",
                        space=SMALL)
    th_opt = co_explore(macro, wl, 5.0, objective="th", method="exhaustive",
                        space=SMALL)
    assert ee[-1] >= ee_opt.metrics["tops_w"] * 0.999
    assert gops[0] >= th_opt.metrics["gops"] * 0.999
