"""The benchmark of the co-exploration service on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of ``BENCHMARK.json``, from the client side of the
served path (``JobQueue.submit`` -> ``ExplorationEngine.run`` -> device ->
``ResultStore`` -> future):

1. set-up (``setup_s``, counted from the start of this process): imports,
   one engine, one queue on a fresh result store, and a warm-up that runs
   every shape the window will use (``Served.warm_up``);
2. the window: the cell's traffic mix for ``--seconds``
   (``bench/loadgen.py``); ``--trace 1`` records a profiler trace of it;
3. after the window: the device's peak memory is read, the service is
   closed, and every job due in the window is compared with the plain
   reference (``bench/check.py``, ``bench/reference.py``);
4. the metrics of ``BENCHMARK.json`` that apply to the cell are read, each
   by its own reader ``bench/metrics/<name>.py``: the end-to-end metrics
   with ``--trace 0``, the per-layer metrics with ``--trace 1``.

The last lines of standard error are the numbers compared, each beside
its limit; the last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, in traced
runs ``breakdown``, and last ``checks`` (the same numbers and limits).
Off a TPU, or with fewer chips than the cell needs, it exits non-zero and
prints no result.  The process starts no other process.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(BENCH, "metrics")]

import cell as cells  # noqa: E402
import check  # noqa: E402
import drive  # noqa: E402
import reduce  # noqa: E402
import tracing  # noqa: E402

EXIT_NO_PROGRAM = 2
EXIT_NO_CHIP = 3


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts executables built while ``active`` (XLA compiles, loads from
    the persistent compilation cache included), by function name, via the
    ``jax.monitoring`` event that wraps every backend compile."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.active = False
        self.names: collections.Counter = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    @property
    def count(self) -> int:
        return sum(self.names.values())

    def _duration(self, name, _secs, fun_name="?", **_kw):
        if self.active and name == self.EVENT:
            self.names[fun_name] += 1


def require_chips(chips: int):
    """The devices of the cell, or exit: a TPU and enough of them."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        _log(f"bench: needs a TPU, JAX found {devices[0].platform!r}")
        raise SystemExit(EXIT_NO_CHIP)
    if len(devices) < chips:
        _log(f"bench: needs {chips} chips, JAX found {len(devices)}")
        raise SystemExit(EXIT_NO_CHIP)
    return devices[:chips]


def _registry():
    from repro import obs

    return obs.registry().snapshot()


def _obs_spans():
    from repro import obs

    return obs.tracer().events()


def _trace_numbers(ext, spans) -> dict:
    """Busy and idle from the device planes, averaged over the chips;
    ``None`` when the trace holds no device plane."""
    if not ext.devices:
        return None
    lo, hi = ext.window
    busy, modules = [], []
    for lines in ext.devices.values():
        busy.append(reduce.busy_ns(lines.get("XLA Ops", []), lo, hi))
        modules.extend(e for e in lines.get("XLA Modules", [])
                       if lo <= e[1] < hi)
    host = [e for e in ext.host
            if e[0] not in (tracing.SYNC, tracing.WINDOW)]
    host += [(s["name"], s["ts"] * 1e3 - ext.offset_ns, s["dur"] * 1e3)
             for s in spans]
    first = next(iter(ext.devices.values())).get("XLA Ops", [])
    gaps = reduce.idle_gaps(first, lo, hi)
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "modules": modules,
        "device_ops": reduce.time_by_name(modules, 10),
        "idle_gaps": reduce.name_gaps(gaps, host, 10),
    }


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float = T_START) -> tuple[dict, list]:
    """One run of ``cell``; returns the result object and the lines of
    numbers compared."""
    from reference import Reference

    counter = CompileCounter()
    method = cell.config["method"]
    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        tr = tracing.DeviceTrace(os.path.join(tmp, "trace")) \
            if trace else None
        served = drive.Served(cell.config, seed, os.path.join(tmp, "store"),
                              annotate=tr.annotate if tr else None,
                              queue_config=drive.queue_config(cell.mix,
                                                              cell.config))
        served.warm_up(cell.mix)
        setup_s = time.perf_counter() - t_start
        reg0 = _registry()
        window = drive.open_window if cell.mix["loop"] == "open" \
            else drive.closed_window
        counter.active = True
        if tr:
            tr.start()
            with tr.window():
                records, window_s = window(served, cell.mix, seed, seconds)
        else:
            records, window_s = window(served, cell.mix, seed, seconds)
        counter.active = False
        reg1 = _registry()
        ext = tr.stop() if tr else None
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        outcomes = [r.outcome() for r in records]
        store_mismatch = 0
        for rec, (res, _err) in zip(records, outcomes):
            if res is None:
                continue
            stored = served.store.get(rec.future.key)
            if stored is None or (
                    (stored.config, stored.metrics, stored.per_op_strategy)
                    != (res.config, res.metrics, res.per_op_strategy)):
                store_mismatch += 1
        served.close()
        spans = _obs_spans() if ext else []
        del served
        gc.collect()

    ref = Reference(cell.config)
    items = [(r.triple, r.budget,
              check.from_result(res) if res is not None else None)
             for r, (res, _e) in zip(records, outcomes)]
    numbers, optimal = check.compare(items, ref, method)
    numbers = {"missing": numbers.pop("missing"),
               "store_mismatch": store_mismatch, **numbers}
    correct, table = check.verdict(numbers, cell.limits)

    run = types.SimpleNamespace(
        cell=cell.name, config=cell.config, mix=cell.mix, method=method,
        seed=seed, seconds=seconds, setup_s=setup_s, window_s=window_s,
        records=records, results=[res for res, _ in outcomes],
        reg0=reg0, reg1=reg1, compiles=counter.count, optimal=optimal,
        trace=_trace_numbers(ext, spans) if ext else None,
        device_kind=devices[0].device_kind)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = cells.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    failed = sum(1 for res, _ in outcomes if res is None)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct), "attempted": len(records),
           "failed": failed, "metrics": metrics, "device": device}
    if run.trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["checks"] = table
    lines = [f"compiles in window: {dict(counter.names)}"]
    lines += [f"check {k}: {v['value']!r} (limit {v['limit']!r})"
              for k, v in table.items()]
    return out, lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        _log(f"bench: the program is not in this checkout ({src})")
        return EXIT_NO_PROGRAM
    sys.path.insert(0, src)
    # the program keeps its compile cache in the checkout when this is
    # unset; a cache at a path from outside would be shared with others
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    devices = require_chips(cell.chips)
    out, lines = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          devices)
    for line in lines:
        _log(line)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
