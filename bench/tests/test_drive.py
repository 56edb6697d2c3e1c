"""A closed-loop sweep reaches the engine as one micro-batch.

Run from the repository root:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests

The queue's default 20 ms micro-batch window splits a sweep whose
``submit_many`` is slower than that; the rest then dispatches as a job
count that set-up never compiled.  The sweep mix's queue settings make a
sweep one dispatch per executable bucket however slow the submit is.
"""
from __future__ import annotations

import os
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(os.path.abspath(__file__))]

import drive  # noqa: E402
import loadgen  # noqa: E402
from test_faults import small  # noqa: E402


def test_sweep_mix_names_its_queue_settings():
    c = small("fig7.sweep")
    q = drive.queue_config(c.mix, c.config)
    assert q["max_batch_jobs"] == len(loadgen.triples(c.config))
    assert q["batch_window_s"] >= 0.5


@pytest.mark.parametrize("from_mix,split", ((True, False), (False, True)),
                         ids=("sweep_mix", "program_default"))
def test_slow_submit_splits_only_the_default_window(tmp_path, monkeypatch,
                                                    from_mix, split):
    c = small("fig7.sweep")
    queue = drive.queue_config(c.mix, c.config) if from_mix else None
    served = drive.Served(c.config, 5, str(tmp_path / "store"),
                          queue_config=queue)
    real = served.queue.submit

    def slow(*a, **kw):
        time.sleep(0.02)
        return real(*a, **kw)

    monkeypatch.setattr(served.queue, "submit", slow)
    all_t = loadgen.triples(c.config)
    try:
        futures = served.submit_many([(t, 3.5) for t in all_t])
        for f in futures:
            f.result(timeout=600)
        dispatches = served.queue.stats_snapshot()["queue"]["dispatches"]
    finally:
        served.close()
    buckets = len(served._buckets(all_t, served.method))
    assert (dispatches > buckets) == split, (dispatches, buckets)
