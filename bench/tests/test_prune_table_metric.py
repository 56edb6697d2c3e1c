"""The reader of the pruning table's lookup counter, on synthetic runs
with known registry deltas.

Run from the repository root (the tier-1 suite collects only ``tests/``):

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import json
import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(BENCH, "metrics")]

import cell as cells  # noqa: E402

NAME = "engine.prune_table_hit_share"
HIT = 'cim_engine_prune_table_total{outcome="hit"}'
MISS = 'cim_engine_prune_table_total{outcome="miss"}'


def _run(hits: float, misses: float, program_has_family: bool = True):
    reg0 = {HIT: 40.0, MISS: 2.0}
    reg1 = {HIT: 40.0 + hits, MISS: 2.0 + misses}
    if not program_has_family:
        reg0, reg1 = {}, {}
    return types.SimpleNamespace(mix={"loop": "closed"}, reg0=reg0,
                                 reg1=reg1, results=[object()] * 28)


@pytest.mark.parametrize("hits, misses, want", [
    (2044.0, 0.0, 100.0),        # every window job found the table
    (27.0, 1.0, 100.0 * 27 / 28),
    (0.0, 3.0, 0.0),
])
def test_reader_gives_the_window_hit_share(hits, misses, want):
    assert cells.reader(NAME)(_run(hits, misses)) == pytest.approx(want)


def test_reader_gives_nothing_for_a_program_without_the_family():
    assert cells.reader(NAME)(_run(5.0, 0.0,
                                   program_has_family=False)) is None


def test_reader_gives_nothing_for_a_window_without_a_lookup():
    assert cells.reader(NAME)(_run(0.0, 0.0)) is None


def test_reader_is_a_benchmark_metric_of_the_exhaustive_cell_only():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    m = {e["name"]: e for e in spec["per_layer"]}[NAME]
    assert (m["source"], m["layer"], m["moves"], m["unit"]) == (
        "program_counter", "engine", "jobs_per_s", "%")
    assert m["workloads"] == ["fig7.sweep"]
    assert m in cells.load_cell("fig7.sweep").per_layer
    assert m not in cells.load_cell("sa.sweep").per_layer
