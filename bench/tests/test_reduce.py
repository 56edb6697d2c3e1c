"""The trace-to-metrics reduction on small traces.

Run from the repository root (the tier-1 suite collects only ``tests/``):

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests

``data/trace_excerpt.json`` is recorded from a traced run of ``fig7.sweep``
on one TPU v5e: the first device operations and modules of the window and
the host spans around them, on the trace's clock (ns).
"""
from __future__ import annotations

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import peaks  # noqa: E402
import reduce  # noqa: E402
import work  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "trace_excerpt.json")


def test_busy_union_merges_overlaps_and_clips_to_the_window():
    ops = [("a", 0, 10), ("b", 5, 10), ("c", 30, 10), ("d", 95, 20)]
    assert reduce.busy_ns(ops, 0, 100) == 15 + 10 + 5
    assert reduce.busy_ns(ops, 8, 35) == 7 + 5


def test_idle_gaps_are_the_complement_of_the_union():
    ops = [("a", 10, 10), ("b", 15, 10), ("c", 50, 5)]
    assert reduce.idle_gaps(ops, 0, 60) == [(0, 10), (25, 50), (55, 60)]
    assert reduce.idle_gaps([], 0, 60) == [(0, 60)]


def test_gaps_are_named_by_the_innermost_covering_host_span():
    gaps = [(0, 10), (25, 50), (55, 60)]
    host = [("bench.wait", 0, 100), ("engine.batch", 20, 40)]
    named = dict(reduce.name_gaps(gaps, host))
    assert named == pytest.approx({"engine.batch": 30e-9, "bench.wait": 10e-9})
    assert reduce.name_gaps([(0, 10)], []) == [[reduce.UNNAMED, 1e-8]]


def test_module_time_and_roofline():
    mods = [("jit_one_job(1)", 0, 2_000_000), ("jit_mul", 0, 1_000_000),
            ("jit_one_job(2)", 5, 3_000_000)]
    secs = reduce.module_seconds(mods, lambda n: "one_job" in n)
    assert secs == pytest.approx(5e-3)
    pk = peaks.peaks("TPU v5 lite")
    flops, nbytes = work.exhaustive_job(4096, 8, 8)
    share = reduce.roofline_pct(flops, nbytes, secs, pk)
    want = 100 * max(flops / 197e12, nbytes / 819e9) / 5e-3
    assert share == pytest.approx(want)
    assert reduce.roofline_pct(flops, nbytes, 0.0, pk) is None
    with pytest.raises(LookupError):
        peaks.peaks("cpu")


def test_work_counts_only_real_candidates_and_admitted_strategies():
    f, b = work.exhaustive_job(5000, 7, 2)
    assert f == pytest.approx(5000 * (7 * 2 * 2234 / 8 + 487))
    assert b == 5000 * 28 + 2 * (7 * 5 + 30) * 4
    f, _ = work.sa_job(64, 400, 7, 8)
    assert f == pytest.approx(64 * 401 * (7 * 8 * 2234 / 8 + 487))


def test_recorded_trace():
    with open(DATA) as f:
        rec = json.load(f)
    lo, hi = rec["window"]
    ops = [tuple(e) for e in rec["ops"]]
    busy = reduce.busy_ns(ops, lo, hi)
    gaps = reduce.idle_gaps(ops, lo, hi)
    assert busy + sum(e - s for s, e in gaps) == pytest.approx(hi - lo)
    assert busy == pytest.approx(rec["expect"]["busy_ns"])
    assert len(gaps) == rec["expect"]["gaps"]
    secs = reduce.module_seconds([tuple(e) for e in rec["modules"]],
                                 lambda n: "one_job" in n)
    assert secs == pytest.approx(rec["expect"]["one_job_s"])
    named = reduce.name_gaps(gaps, [tuple(e) for e in rec["host"]])
    assert named[0][0] == rec["expect"]["top_gap_name"]
