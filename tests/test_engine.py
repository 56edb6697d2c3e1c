"""Batched exploration engine: per-job equivalence, caching, bucketing."""
import numpy as np
import pytest

from repro.core import (
    DesignSpace,
    ExplorationEngine,
    ExploreJob,
    SASettings,
    bert_large_workload,
    co_explore,
    co_explore_macros,
    get_macro,
)
from repro.core.macro import TPDCIM_MACRO, TRANCIM_MACRO

SMALL = DesignSpace(mr=(1, 2, 3), mc=(1, 2), scr=(1, 4, 16),
                    is_kb=(2, 16, 128), os_kb=(2, 16, 64))


def _heterogeneous_jobs():
    """3+ jobs differing in macro, workload, objective AND strategy set."""
    from repro.configs import get_arch
    return [
        ExploreJob(TPDCIM_MACRO, bert_large_workload(), 2.23,
                   objective="ee", space=SMALL),
        ExploreJob(get_macro("vanilla-dcim"),
                   get_arch("yi-6b").workload(seq=512), 5.0,
                   objective="th", space=SMALL),
        ExploreJob(TRANCIM_MACRO, get_arch("whisper-small").workload(seq=512),
                   3.52, objective="ee", strategy_set="so", space=SMALL),
        ExploreJob(get_macro("lcc-cim"), bert_large_workload(), 3.0,
                   objective="edp", space=SMALL),
    ]


@pytest.mark.parametrize("method", ["exhaustive", "sa"])
def test_batched_matches_per_job_co_explore(method):
    """The batched engine must return the SAME best configs/metrics as the
    sequential per-job path (a batch of one) on heterogeneous jobs."""
    jobs = _heterogeneous_jobs()
    settings = SASettings(n_chains=16, n_steps=100, seed=3)
    engine = ExplorationEngine()
    batched = engine.run(jobs, method=method, sa_settings=settings)
    for job, b in zip(jobs, batched):
        s = co_explore(job.macro, job.workload, job.area_budget_mm2,
                       objective=job.objective,
                       strategy_set=job.strategy_set, method=method,
                       space=SMALL, sa_settings=settings)
        assert b.config.as_tuple() == s.config.as_tuple(), (method, job)
        for key in ("energy_pj", "latency_cycles", "tops_w", "gops"):
            assert b.metrics[key] == pytest.approx(s.metrics[key], rel=1e-9)
        assert b.metrics["area_mm2"] <= job.area_budget_mm2 * 1.001


def test_executable_cache_hits_on_resubmission():
    jobs = _heterogeneous_jobs()
    settings = SASettings(n_chains=8, n_steps=40, seed=0)
    engine = ExplorationEngine()
    first = engine.run(jobs, method="sa", sa_settings=settings)
    misses = engine.stats["executable_cache_misses"]
    again = engine.run(jobs, method="sa", sa_settings=settings)
    assert engine.stats["executable_cache_misses"] == misses, \
        "repeat submission must not build new executables"
    assert engine.stats["executable_cache_hits"] > 0
    for a, b in zip(first, again):
        assert a.config.as_tuple() == b.config.as_tuple()
        assert a.metrics["energy_pj"] == b.metrics["energy_pj"]


def test_bucketing_pads_are_cost_transparent():
    """Jobs bucketed together (padded operator arrays) score identically to
    solo runs: padded rows carry count == 0 and contribute nothing."""
    from repro.configs import get_arch
    wl_small = bert_large_workload()                 # few merged ops
    wl_big = get_arch("whisper-small").workload(seq=512)  # many (cross-attn)
    engine = ExplorationEngine()
    solo = engine.run(
        [ExploreJob(TPDCIM_MACRO, wl_small, 2.23, space=SMALL)],
        method="exhaustive")[0]
    mixed = engine.run(
        [ExploreJob(TPDCIM_MACRO, wl_small, 2.23, space=SMALL),
         ExploreJob(TPDCIM_MACRO, wl_big, 2.23, space=SMALL)],
        method="exhaustive")[0]
    assert solo.config.as_tuple() == mixed.config.as_tuple()
    assert solo.metrics["energy_pj"] == mixed.metrics["energy_pj"]


def test_macro_library_runs_as_one_batch():
    """co_explore_macros stacks per-macro jobs into one engine batch (macro
    constants are per-job arrays inside a shared executable)."""
    engine = ExplorationEngine()
    wl = bert_large_workload()
    macros = [get_macro("vanilla-dcim"), get_macro("lcc-cim")]
    best, results = co_explore_macros(
        macros, wl, 3.0, objective="ee", method="exhaustive", space=SMALL,
        engine=engine)
    assert engine.stats["jobs"] == 2
    assert engine.stats["batches"] == 1
    assert best.metrics["tops_w"] == max(r.metrics["tops_w"]
                                         for r in results)


def test_search_stats_reported():
    engine = ExplorationEngine()
    res = engine.run(
        [ExploreJob(TPDCIM_MACRO, bert_large_workload(), 2.23, space=SMALL)],
        method="exhaustive")[0]
    assert res.search["method"] == "exhaustive"
    assert res.search["batch_jobs"] == 1
    assert res.search["runtime_s"] > 0
    assert res.search["kept"] > 0                    # prune stats forwarded


def test_candidate_values_match_objective():
    """candidate_values (the Pareto path) equals the argmin path's scores."""
    from repro.core.pruning import candidates_with_bw, prune_space
    job = ExploreJob(TPDCIM_MACRO, bert_large_workload(), 2.23, space=SMALL)
    engine = ExplorationEngine()
    cands, _ = prune_space(SMALL, job.macro, job.area_budget_mm2, job.bw,
                           job.tech)
    rows = candidates_with_bw(cands, job.bw)
    vals = engine.candidate_values([job], [rows])[0]
    assert len(vals) == len(rows)
    best = engine.run([job], method="exhaustive")[0]
    np_best = rows[int(np.argmin(vals))]
    assert tuple(int(x) for x in np_best[:5]) == best.config.as_tuple()


# ------------------------------------------------------------------ #
# phase spans, ancestry, retrace counter
# ------------------------------------------------------------------ #
_RUN_PHASES = ("engine.prepare", "engine.prune", "engine.executable",
               "engine.finish")


def _phase_sums() -> dict:
    from repro import obs
    snap = obs.registry().snapshot()
    return {k: v for k, v in snap.items()
            if k.startswith(("cim_engine_phase_seconds_sum",
                             "cim_engine_run_seconds_sum"))}


def _descendants(events: list, root_id: str) -> list:
    """Events whose parent chain reaches ``root_id``."""
    by_id = {e["id"]: e for e in events}
    out = []
    for e in events:
        p = e["args"].get("parent")
        while p is not None and p != root_id:
            p = by_id[p]["args"].get("parent") if p in by_id else None
        if p == root_id:
            out.append(e)
    return out


@pytest.mark.parametrize("method", ["exhaustive", "sa"])
def test_phase_spans_tile_one_run_without_overlap(method):
    from repro import obs
    from repro.core import job_key
    jobs = [ExploreJob(TPDCIM_MACRO, bert_large_workload(), b,
                       objective="ee", space=SMALL) for b in (2.0, 2.5)]
    settings = SASettings(n_chains=8, n_steps=20, seed=1)
    engine = ExplorationEngine()
    keys = [job_key(j, method, None if method == "exhaustive" else settings)
            for j in jobs]
    engine.run(jobs, method=method, settings=settings
               if method == "sa" else None)          # compile outside
    before = _phase_sums()
    with obs.span("test.dispatch", batch=7):
        engine.run(jobs, method=method, keys=keys,
                   settings=settings if method == "sa" else None)
    after = _phase_sums()
    events = obs.tracer().events()
    run = [e for e in events if e["name"] == "engine.run"][-1]
    assert run["args"]["batch"] == 7 and "parent" in run["args"]
    inside = _descendants(events, run["id"])
    phases = sorted((e for e in inside if e["name"] in _RUN_PHASES),
                    key=lambda e: e["ts"])
    names = {e["name"] for e in phases}
    assert {"engine.prepare", "engine.executable", "engine.finish"} <= names
    assert ("engine.prune" in names) == (method == "exhaustive")
    for a, b in zip(phases, phases[1:]):          # never nest or overlap
        assert a["ts"] + a["dur"] <= b["ts"] + 1e-3, (a, b)
    for e in inside:
        assert e["args"]["batch"] == 7
    per_job = [e for e in phases if e["name"] in ("engine.prune",
                                                  "engine.finish")]
    assert sorted(e["args"]["job"] for e in per_job) == sorted(
        keys * (2 if method == "exhaustive" else 1))
    grew = {k: after[k] - before.get(k, 0.0) for k in after}
    run_s = grew.pop("cim_engine_run_seconds_sum")
    assert 0 < sum(grew.values()) <= run_s
    assert run_s * 1e6 <= run["dur"] + 1.0


def test_retrace_counter_sees_each_new_job_count():
    from repro import obs
    from repro.core.engine import _M_TRACES
    jobs = [ExploreJob(TPDCIM_MACRO, bert_large_workload(), 2.0 + 0.1 * i,
                       space=SMALL) for i in range(3)]
    rows = np.array([[1, 1, 1, 2, 2, 256], [2, 1, 4, 16, 16, 256]], float)
    engine = ExplorationEngine()
    counter = _M_TRACES.labels(executable="one_job_sweep")

    def sweep(n):
        n0 = counter.value
        with obs.span("test.sweep") as sp:
            engine.candidate_values(jobs[:n], [rows] * n)
        spans = [e for e in _descendants(obs.tracer().events(), sp.span_id)
                 if e["name"] == "engine.compile"]
        return counter.value - n0, spans

    traced2, spans2 = sweep(2)
    traced3, spans3 = sweep(3)
    assert traced2 + traced3 == 2
    assert [s["args"]["J"] for s in spans2 + spans3] == [2, 3]
    assert {s["args"]["executable"] for s in spans2 + spans3} == {
        "one_job_sweep"}
    assert all(s["args"]["parent"] for s in spans2 + spans3)
    assert sweep(2) == (0, [])


def test_executables_are_named_by_what_they_run():
    engine = ExplorationEngine()
    from repro.search.base import get_backend
    sweep = engine._exhaustive_executable(8).__wrapped__
    sa = engine._search_executable(get_backend("sa"), 8, 8,
                                   SASettings(n_chains=4, n_steps=4))
    assert sweep.__name__ == "one_job_sweep"
    assert sa.__wrapped__.__name__ == "one_job_sa"


def test_snap_fallback_is_its_own_span_outside_finish():
    from repro import obs
    engine = ExplorationEngine()
    job = ExploreJob(TPDCIM_MACRO, bert_large_workload(), 2.0, space=SMALL)
    p = engine._prepare(job)._replace(key="k-fallback")
    largest = (np.asarray(p.lens) - 1)[None, :]   # over any small budget
    with obs.span("test.winner") as sp:
        out = engine._wrap_search_winner(p, "sa", largest,
                                         np.array([1.0]), np.zeros(3))
    assert "kept" in out.search, "the winner must have fallen back"
    inside = _descendants(obs.tracer().events(), sp.span_id)
    (fb,) = [e for e in inside if e["name"] == "engine.fallback"]
    (fin,) = [e for e in inside if e["name"] == "engine.finish"]
    kids = [e for e in inside if e["args"]["parent"] == fb["id"]]
    assert {e["name"] for e in kids} >= {"engine.prune", "engine.executable"}
    for e in kids + [fb, fin]:
        assert e["args"]["job"] == "k-fallback"
    assert all(e["args"]["fallback"] is True for e in kids
               if e["name"] != "engine.compile")
    assert fin["args"]["parent"] == sp.span_id and "fallback" not in \
        fin["args"]
    assert fb["ts"] + fb["dur"] <= fin["ts"] + 1e-3


# ------------------------------------------------------------------ #
# the jitted result epilogue (finish_metrics)
# ------------------------------------------------------------------ #
def _two_width_jobs():
    """Two networks whose merged operators pad to different widths (bert
    5 ops -> 8, whisper-small 13 ops -> 16)."""
    from repro.configs import get_arch
    return [
        ExploreJob(TPDCIM_MACRO, bert_large_workload(), 2.23,
                   objective="ee", space=SMALL),
        ExploreJob(get_macro("vanilla-dcim"),
                   get_arch("whisper-small").workload(seq=512), 5.0,
                   objective="th", strategy_set="so", space=SMALL),
    ]


@pytest.mark.parametrize("method", ["exhaustive", "sa"])
def test_epilogue_matches_eager_workload_metrics(method):
    from repro.core import cost_model
    from repro.core.strategies import ALL_STRATEGIES
    jobs = _two_width_jobs()
    engine = ExplorationEngine()
    assert {engine._prepare(j).ops_pad for j in jobs} == {8, 16}
    results = engine.run(jobs, method=method,
                         sa_settings=SASettings(n_chains=8, n_steps=40,
                                                seed=2))
    for job, r in zip(jobs, results):
        wl = job.merged_workload()
        c = r.config
        eager = cost_model.workload_metrics(
            wl.as_arrays(),
            np.array([c.mr, c.mc, c.scr, c.is_kb, c.os_kb, c.bw], float),
            job.macro, job.tech, job.objective, job.strategy_set)
        idx = eager.pop("strategy_idx")
        assert set(r.metrics) == set(eager)
        for key, value in eager.items():
            assert r.metrics[key] == pytest.approx(value, rel=1e-6), key
        assert r.per_op_strategy == {
            op.name or f"op{i}": str(ALL_STRATEGIES[idx[i]])
            for i, op in enumerate(wl.ops)}


@pytest.mark.parametrize("network", ["bert-large", "whisper-small"])
def test_padded_rows_leave_workload_metrics_core_unchanged(network):
    import jax

    from repro.configs import get_arch
    from repro.core import cost_model
    from repro.core.engine import _job_arrays
    wl = (bert_large_workload() if network == "bert-large"
          else get_arch(network).workload(seq=512))
    engine = ExplorationEngine()
    p = engine._prepare(ExploreJob(TPDCIM_MACRO, wl, 3.0, space=SMALL))
    n = len(p.workload.ops)
    cfg_row = np.array([2.0, 2.0, 4.0, 16.0, 16.0, 256.0])
    core = jax.jit(cost_model.workload_metrics_core)
    outs = [core(_job_arrays(p._replace(ops_pad=width)), cfg_row)
            for width in (n, 2 * n)]
    (lat, en, idx, area, true_ops), padded = outs
    assert idx.shape == (n,) and padded[2].shape == (2 * n,)
    np.testing.assert_array_equal(padded[2][:n], idx)
    for base, pad in ((lat, padded[0]), (en, padded[1]),
                      (true_ops, padded[4])):
        assert float(pad) == pytest.approx(float(base), rel=1e-6)
    assert float(padded[3]) == float(area)
    assert float(true_ops) == 2.0 * sum(
        op.m * op.k * op.n * op.count for op in p.workload.ops)


def test_epilogue_compiles_once_per_operator_bucket():
    from repro.configs import get_arch
    from repro.core.engine import _M_TRACES
    counter = _M_TRACES.labels(executable="finish_metrics")
    engine = ExplorationEngine()
    bert = ExploreJob(TPDCIM_MACRO, bert_large_workload(), 2.23,
                      space=SMALL)
    yi = ExploreJob(get_macro("vanilla-dcim"),
                    get_arch("yi-6b").workload(seq=512), 5.0, space=SMALL)
    assert engine._prepare(bert).ops_pad == engine._prepare(yi).ops_pad == 8
    engine.run([bert], method="exhaustive")
    traced = counter.value
    finish_keys = [k for k in engine._executables if k[0] == "finish"]
    assert len(finish_keys) == 1
    engine.run([bert], method="exhaustive")
    engine.run([yi], method="exhaustive")
    engine.run([bert, yi], method="exhaustive")
    assert counter.value == traced
    assert [k for k in engine._executables if k[0] == "finish"] == \
        finish_keys


def test_epilogue_module_is_not_a_cost_executable():
    """The roofline readers sum every device module named ``one_job``;
    the epilogue's must not be one of them."""
    from repro.core.engine import _finish_row
    from repro.core.template import AcceleratorConfig
    engine = ExplorationEngine()
    fn = engine._finish_executable(8).__wrapped__
    assert fn.__name__ == "finish_metrics"
    p = engine._prepare(ExploreJob(TPDCIM_MACRO, bert_large_workload(),
                                   2.23, space=SMALL))
    row = _finish_row(p, AcceleratorConfig(1, 1, 1, 2, 2, bw=256))
    text = fn.lower(row).as_text()
    assert "jit_finish_metrics" in text
    assert "one_job" not in text
