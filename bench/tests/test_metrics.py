"""The readers of the program's phase histograms and trace counter, on
synthetic runs with known registry deltas.

Run from the repository root (the tier-1 suite collects only ``tests/``):

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(BENCH, "metrics")]

import cell as cells  # noqa: E402

JOBS = 10


def _phase(family: str, phase: str) -> str:
    return f'{family}_sum{{phase="{phase}"}}'


E, Q = "cim_engine_phase_seconds", "cim_queue_phase_seconds"
#: seconds each phase grew by in the window (and what it read before)
GREW = {_phase(E, "prepare"): 0.2, _phase(E, "bucket"): 0.1,
        _phase(E, "prune"): 1.5, _phase(E, "executable"): 0.05,
        _phase(E, "finish"): 2.5, _phase(Q, "submit"): 0.004,
        _phase(Q, "resolve"): 0.08, "cim_engine_run_seconds_sum": 5.0,
        'cim_engine_traces_total{executable="one_job_sweep"}': 1.0,
        'cim_engine_traces_total{executable="one_job_sa"}': 2.0}


def _run(loop: str = "closed", program_has_phases: bool = True,
         jobs: int = JOBS):
    reg0 = {k: 7.0 for k in GREW}
    reg1 = {k: 7.0 + v for k, v in GREW.items()}
    if not program_has_phases:
        for reg in (reg0, reg1):
            for k in list(reg):
                if "phase" in k or "traces" in k:
                    del reg[k]
    return types.SimpleNamespace(mix={"loop": loop}, reg0=reg0, reg1=reg1,
                                 results=[object()] * jobs + [None])


WANT = {
    "engine.prepare_s_per_job": (0.2 + 0.1) / JOBS,
    "engine.prune_s_per_job": 1.5 / JOBS,
    "engine.executable_s_per_job": 0.05 / JOBS,
    "engine.finish_s_per_job": 2.5 / JOBS,
    "engine.self_s_per_job": (5.0 - 0.2 - 1.5 - 0.05 - 2.5) / JOBS,
    "engine.retraces_in_window": 3.0,
    "queue.submit_ms_per_job": 1e3 * 0.004 / JOBS,
    "queue.resolve_ms_per_job": 1e3 * 0.08 / JOBS,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_gives_the_window_delta_per_job(name):
    assert cells.reader(name)(_run()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_gives_nothing_for_a_program_without_the_family(name):
    assert cells.reader(name)(_run(program_has_phases=False)) is None


@pytest.mark.parametrize("name", sorted(set(WANT) - {
    "engine.retraces_in_window"}))
def test_per_job_reader_needs_a_closed_window_with_jobs(name):
    read = cells.reader(name)
    assert read(_run(loop="open")) is None
    assert read(_run(jobs=0)) is None


def test_every_new_reader_is_a_benchmark_metric_in_its_cells():
    import json
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    entries = {m["name"]: m for m in spec["per_layer"]}
    for name in WANT:
        m = entries[name]
        assert m["source"] == "program_counter"
        assert m["moves"] == "jobs_per_s"
        want = ["fig7.sweep"] if name == "engine.prune_s_per_job" else [
            "fig7.sweep", "sa.sweep"]
        assert m["workloads"] == want
        for cell in want:
            assert m in cells.load_cell(cell).per_layer
