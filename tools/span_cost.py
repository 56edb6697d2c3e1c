#!/usr/bin/env python
"""Host cost of one ``repro.obs`` phase span, in microseconds.

    PYTHONPATH=src python tools/span_cost.py [--spans 20000]

Times ``--spans`` empty ``with obs.span(...)`` blocks, each observed in a
histogram child and carrying a ``job`` arg like the engine's phase spans
(enter, exit, ring-buffer record, histogram observation), three ways: before
JAX is imported, with JAX imported and no profiler running (each span then
also makes one ``jax.profiler.TraceAnnotation``), and while a JAX profile
runs (the annotation is then recorded too).  Prints one JSON line.  The
profiled case starts JAX's profiler, so on a machine with an accelerator it
takes the device like any JAX process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

from repro.obs.metrics import Registry  # noqa: E402
from repro.obs.trace import Tracer  # noqa: E402


def us_per_span(n: int) -> float:
    tr = Tracer(capacity=8192, jsonl_path="")
    h = Registry().histogram("span_cost_seconds", "x", ("phase",))
    child = h.labels(phase="finish")
    t0 = time.perf_counter()
    for _ in range(n):
        with tr.span("engine.finish", histogram=child, job="k"):
            pass
    return (time.perf_counter() - t0) / n * 1e6


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--spans", type=int, default=20000)
    args = ap.parse_args(argv)
    out = {"spans": args.spans, "no_jax_us": us_per_span(args.spans)}
    import jax

    out["jax_no_profiler_us"] = us_per_span(args.spans)
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        try:
            out["profiler_us"] = us_per_span(args.spans)
        finally:
            jax.profiler.stop_trace()
    out["platform"] = jax.devices()[0].platform
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
