"""The harness's comparison against its control and against faults.

Run from the repository root (the tier-1 suite collects only ``tests/``):

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests

- the bfloat16 control is not correct under every cell's limits;
- a whole run on the CPU (the harness's look for a chip skipped) at a small
  design space is correct as it stands, and not correct with the timed path
  broken underneath: an answer altered where the engine produces it, and
  half of a batch answered with the other half's results.
"""
from __future__ import annotations

import json
import os
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(BENCH, "metrics"),
                os.path.join(ROOT, "src")]

import cell as cells  # noqa: E402
import control  # noqa: E402

CELLS = ("fig7.sweep", "sa.sweep")
SMALL_SPACE = {"mr": [1, 2, 3], "mc": [1, 2], "scr": [1, 4, 16],
               "is_kb": [2, 16, 128], "os_kb": [2, 16, 64]}


def small(name: str, mix: str | None = None,
          networks=("bert-large", "whisper-small")):
    """A cell at a small design space; ``mix`` swaps in another traffic
    file (the open-loop mix has no cell of its own yet)."""
    c = cells.load_cell(name)
    if mix is not None:
        with open(os.path.join(BENCH, "traffic", mix + ".json")) as f:
            c.mix = json.load(f)
    c.config["design_space"] = SMALL_SPACE
    c.config["networks"] = {n: c.config["networks"][n] for n in networks}
    c.mix["warm_jobs_per_dispatch"] = min(
        2, c.mix.get("warm_jobs_per_dispatch", 0))
    if c.mix["loop"] == "open":
        c.mix["rate_per_s"] = 1.0
    return c


@pytest.mark.parametrize("name,mix", [(c, None) for c in CELLS]
                         + [("sa.sweep", "open")])
@pytest.mark.parametrize("seed", (1, 2**31 + 17))
def test_control_is_not_correct(name, mix, seed):
    correct, table = control.run_control(small(name, mix), seed, sweeps=2,
                                         seconds=8.0)
    assert not correct, table


def _run(name: str, mix: str | None = None, seed: int = 5):
    import jax

    import run

    out, _ = run.run_cell(small(name, mix), seed, 4.0, False, jax.devices(),
                          t_start=time.perf_counter())
    return out


@pytest.fixture
def engine_run(monkeypatch):
    """Wrap ``ExplorationEngine.run`` with a fault that strikes once the
    window opens (set-up stays sound); returns the setter."""
    import drive
    from repro.core import ExplorationEngine

    real = ExplorationEngine.run
    armed = {"on": False}
    for name in ("closed_window", "open_window"):
        window = getattr(drive, name)

        def opened(*a, _window=window, **kw):
            armed["on"] = True
            return _window(*a, **kw)
        monkeypatch.setattr(drive, name, opened)

    def install(fault):
        def broken(self, jobs, *a, **kw):
            out = real(self, jobs, *a, **kw)
            return fault(out) if armed["on"] else out
        monkeypatch.setattr(ExplorationEngine, "run", broken)
    return install


def _altered(results):
    for r in results:
        r.metrics["energy_pj"] *= 1.001
    return results


def _half(results):
    half = max(1, len(results) // 2)
    return [results[i % half] for i in range(len(results))]


@pytest.mark.parametrize("name,mix", [("fig7.sweep", None),
                                      ("sa.sweep", "open")])
def test_sound_run_is_correct(name, mix):
    out = _run(name, mix)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("name", ("fig7.sweep", "sa.sweep"))
@pytest.mark.parametrize("fault", (_altered, _half),
                         ids=("answer_altered", "half_batch"))
def test_broken_path_is_not_correct(name, fault, engine_run):
    engine_run(fault)
    out = _run(name)
    assert not out["correct"], out["checks"]
