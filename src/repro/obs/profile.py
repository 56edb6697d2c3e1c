"""Kernel profiling tier: wall-clock histograms + roofline utilization.

Profiling hooks around the Pallas kernel wrappers (``repro.kernels.ops``)
record, per ``(kernel, bucket)`` series:

``cim_kernel_us`` (histogram)
    Wall-clock per call in microseconds (``block_until_ready`` timed), with
    the producing span's id as the bucket exemplar -- a latency outlier in
    ``/v1/metrics`` links to its span in ``/v1/trace``.
``cim_kernel_flops_per_call`` / ``cim_kernel_bytes_per_call`` (gauges)
    XLA's compiled cost analysis (via
    :func:`repro.compat.compiled_cost_analysis`), computed once per series.
``cim_kernel_roofline_utilization`` (gauge)
    Achieved FLOP/s over the roofline-attainable rate
    ``min(peak_flops, peak_bw * arithmetic_intensity)`` -- the measurement
    substrate the ROADMAP calibration tier fits correction factors from.

Everything is gated on ``CIM_TUNER_PROFILE`` (checked per call, so the
hooks cost one env lookup when off).  Peak rates come from :data:`PEAKS`,
one table keyed by ``jax.Device.device_kind`` with each entry's source;
a device kind missing from it (the CPU, where kernels run interpreted)
gets no roofline gauge, and :func:`device_peaks` raises for it.

This module is a STABLE PUBLIC SURFACE (re-exported from ``repro.obs``):
:func:`run_microbench` is the measurement half of the calibration tier --
it times the real Pallas kernels over a small tiling sweep and returns
:class:`MeasurementRecord` dicts with the documented schema

    {"kernel": str,   # cim_matmul | flash_attention | selective_scan
                      # | strategy_eval
     "bucket": str,   # shape bucket, e.g. "128x128x128"
     "tiling": str,   # tiling variant, e.g. "AF", "bq64xbk64", "ct16xci128"
     "us":     float, # one call's wall clock, microseconds
     "flops":  float | None,   # compiled cost analysis (None: unavailable)
     "bytes":  float | None,
     "seed":   int}   # RNG seed the inputs were drawn from

which ``repro.core.calibration.fit_corrections`` consumes.  Names with a
leading underscore (``_cost_analysis``, ``_device_kind``, ...) are
implementation details and may change without notice.
"""
from __future__ import annotations

import os
import threading
import time
import typing

from repro.obs import metrics as _metrics
from repro.obs import trace as _trace

__all__ = [
    "PROFILE_ENV",
    "KERNEL_US_BUCKETS",
    "MeasurementRecord",
    "profiling_enabled",
    "instrument",
    "roofline_utilization",
    "DevicePeaks",
    "PEAKS",
    "UnknownDeviceError",
    "device_peaks",
    "summary",
    "run_microbench",
    "record_measurements",
    "take_measurements",
]

PROFILE_ENV = "CIM_TUNER_PROFILE"

#: per-call kernel wall clock is microseconds, not seconds -- interpret
#: mode on CPU reaches well into the ms range, compiled TPU kernels sit
#: in the single-digit us range
KERNEL_US_BUCKETS = (1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
                     500.0, 1e3, 2.5e3, 5e3, 1e4, 2.5e4, 1e5, 2.5e5, 1e6)



class DevicePeaks(typing.NamedTuple):
    """Published per-chip peaks one roofline is drawn against."""

    flops: float        # bf16 FLOP/s
    bw: float           # HBM bytes/s
    source: str


#: peaks keyed by ``jax.Device.device_kind``
PEAKS: dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(197e12, 819e9,
                               "Google Cloud TPU v5e documentation"),
}


class UnknownDeviceError(LookupError):
    """A device kind with no entry in :data:`PEAKS`."""

_REG = _metrics.registry()
_M_US = _REG.histogram(
    "cim_kernel_us", "Per-call kernel wall clock (microseconds)",
    ("kernel", "bucket"), buckets=KERNEL_US_BUCKETS)
_M_FLOPS = _REG.gauge(
    "cim_kernel_flops_per_call",
    "Compiled cost analysis: FLOPs per kernel call", ("kernel", "bucket"))
_M_BYTES = _REG.gauge(
    "cim_kernel_bytes_per_call",
    "Compiled cost analysis: bytes accessed per kernel call",
    ("kernel", "bucket"))
_M_ROOF = _REG.gauge(
    "cim_kernel_roofline_utilization",
    "Achieved FLOP/s over the roofline-attainable rate",
    ("kernel", "bucket"))
_M_RUNTIME = _REG.gauge(
    "cim_kernel_profile_runtime_seconds",
    "Wall clock of the last kernel micro-profile pass")

#: one cost analysis per (kernel, bucket); None caches failures so a
#: non-lowerable callable is probed once, not per call
_COST_CACHE: dict[tuple[str, str], tuple[float, float] | None] = {}
_COST_LOCK = threading.Lock()


def profiling_enabled() -> bool:
    """Whether ``CIM_TUNER_PROFILE`` turns the kernel hooks on."""
    return os.environ.get(PROFILE_ENV, "") not in ("", "0", "false", "no")


def _device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


def device_peaks(device_kind: str | None = None) -> DevicePeaks:
    """Peaks of ``device_kind`` (default: the first visible JAX device's).
    Raises :class:`UnknownDeviceError` for a kind not in :data:`PEAKS`."""
    kind = _device_kind() if device_kind is None else device_kind
    try:
        return PEAKS[kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no published peaks for device kind {kind!r} "
            f"(known: {sorted(PEAKS)})") from None


def roofline_utilization(flops: float, nbytes: float, seconds: float,
                         peaks: DevicePeaks) -> float:
    """Achieved FLOP/s over the roofline-attainable rate for one call.

    Attainable is ``min(peaks.flops, peaks.bw * intensity)`` with
    ``intensity = flops / nbytes``; zero-byte kernels are compute-bound
    by definition."""
    if seconds <= 0 or flops <= 0:
        return 0.0
    achieved = flops / seconds
    if nbytes > 0:
        attainable = min(peaks.flops, peaks.bw * (flops / nbytes))
    else:
        attainable = peaks.flops
    return achieved / attainable if attainable > 0 else 0.0


def _cost_analysis(kernel: str, bucket: str, fn, args,
                   kwargs) -> tuple[float, float] | None:
    """(flops, bytes accessed) of one jitted call, cached per series."""
    key = (kernel, bucket)
    with _COST_LOCK:
        if key in _COST_CACHE:
            return _COST_CACHE[key]
    result = None
    lower = getattr(fn, "lower", None)
    if callable(lower):
        try:
            from repro.compat import compiled_cost_analysis
            ca = compiled_cost_analysis(lower(*args, **kwargs).compile())
            result = (float(ca.get("flops", 0.0) or 0.0),
                      float(ca.get("bytes accessed", 0.0) or 0.0))
        except Exception:        # noqa: BLE001 -- profiling never raises
            result = None
    with _COST_LOCK:
        _COST_CACHE[key] = result
    return result


def profiled_call(kernel: str, fn, bucket: str, args: tuple,
                  kwargs: dict):
    """Run ``fn(*args, **kwargs)`` timed to completion, recording the
    ``cim_kernel_*`` series for ``(kernel, bucket)``."""
    import jax

    with _trace.span(f"kernel.{kernel}", cat="kernel", kernel=kernel,
                     bucket=bucket) as sp:
        out = fn(*args, **kwargs)
        jax.block_until_ready(out)
    _M_US.observe(sp.duration_s * 1e6,
                  exemplar={"span_id": sp.span_id},
                  kernel=kernel, bucket=bucket)
    cost = _cost_analysis(kernel, bucket, fn, args, kwargs)
    if cost is not None:
        flops, nbytes = cost
        _M_FLOPS.set(flops, kernel=kernel, bucket=bucket)
        _M_BYTES.set(nbytes, kernel=kernel, bucket=bucket)
        peaks = PEAKS.get(_device_kind())
        if peaks is not None:
            _M_ROOF.set(roofline_utilization(flops, nbytes, sp.duration_s,
                                             peaks),
                        kernel=kernel, bucket=bucket)
    return out


def instrument(kernel: str, fn, bucket_fn) -> typing.Callable:
    """Wrap one kernel entry point with the profiling hook.

    ``bucket_fn(*args, **kwargs) -> str`` derives the shape-bucket label;
    with profiling off the wrapper is a single env lookup, so the
    default path stays effectively free."""
    def wrapper(*args, **kwargs):
        if not profiling_enabled():
            return fn(*args, **kwargs)
        return profiled_call(kernel, fn, bucket_fn(*args, **kwargs),
                             args, kwargs)
    wrapper.__name__ = getattr(fn, "__name__", kernel)
    wrapper.__qualname__ = getattr(fn, "__qualname__", kernel)
    wrapper.__doc__ = getattr(fn, "__doc__", None)
    wrapper.__wrapped__ = fn
    wrapper.__bucket_fn__ = bucket_fn
    return wrapper


class MeasurementRecord(typing.TypedDict):
    """One timed kernel call -- the calibration tier's unit of evidence.

    The documented schema (see the module docstring): ``kernel``,
    ``bucket``, ``tiling``, ``us``, ``flops``, ``bytes``, ``seed``.
    ``flops``/``bytes`` are ``None`` when XLA's compiled cost analysis
    was unavailable for the series (the fit skips such records)."""
    kernel: str
    bucket: str
    tiling: str
    us: float
    flops: typing.Optional[float]
    bytes: typing.Optional[float]
    seed: int


def summary(records: typing.Sequence[MeasurementRecord] | None = None,
            ) -> list[dict]:
    """Per-(kernel, bucket) profile rows, sorted: call count, mean
    microseconds, FLOPs/bytes and roofline utilization (0.0 when cost
    analysis was unavailable, ``None`` on a device kind with no
    :data:`PEAKS` entry).

    With ``records`` (e.g. the return of :func:`run_microbench`) the rows
    aggregate exactly those measurements; without, they come from the
    process-wide metrics registry (everything profiled so far)."""
    peaks = PEAKS.get(_device_kind())
    if records is not None:
        acc: dict[tuple[str, str], list[MeasurementRecord]] = {}
        for r in records:
            acc.setdefault((r["kernel"], r["bucket"]), []).append(r)
        rows = []
        for (kernel, bucket), group in acc.items():
            us = sum(r["us"] for r in group) / len(group)
            flops = next((r["flops"] for r in group
                          if r["flops"] is not None), 0.0) or 0.0
            nbytes = next((r["bytes"] for r in group
                           if r["bytes"] is not None), 0.0) or 0.0
            rows.append({
                "kernel": kernel,
                "bucket": bucket,
                "calls": len(group),
                "us_per_call": us,
                "flops": flops,
                "bytes": nbytes,
                "roofline_utilization": None if peaks is None else
                roofline_utilization(flops, nbytes, us * 1e-6, peaks),
            })
        rows.sort(key=lambda r: (r["kernel"], r["bucket"]))
        return rows
    rows = []
    for values, child in _M_US.samples():
        kernel, bucket = values
        s, n = child.snapshot()
        if n == 0:
            continue
        rows.append({
            "kernel": kernel,
            "bucket": bucket,
            "calls": n,
            "us_per_call": s / n,
            "flops": _M_FLOPS.value(kernel=kernel, bucket=bucket),
            "bytes": _M_BYTES.value(kernel=kernel, bucket=bucket),
            "roofline_utilization": None if peaks is None else
            _M_ROOF.value(kernel=kernel, bucket=bucket),
        })
    rows.sort(key=lambda r: (r["kernel"], r["bucket"]))
    return rows


# --------------------------------------------------------------------- #
# standard micro-profile pass
# --------------------------------------------------------------------- #
_ALL_KERNELS = ("cim_matmul", "flash_attention", "selective_scan",
                "strategy_eval")


def _microbench_cases(kernels: tuple[str, ...], rng) -> list[tuple]:
    """(kernel, tiling, fn, args, kwargs) cases for the tiling sweep.

    Inputs are drawn once from ``rng`` (shared across tiling variants of
    a kernel) so variant timings differ only by tiling, not data."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    cases: list[tuple] = []
    if "cim_matmul" in kernels:
        a = jnp.asarray(rng.standard_normal((128, 128)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((128, 128)), jnp.float32)
        for tiling in ("AF", "PF"):
            cases.append(("cim_matmul", tiling, ops.cim_matmul, (a, b),
                          {"tiling": tiling}))
    if "flash_attention" in kernels:
        q = jnp.asarray(rng.standard_normal((1, 128, 64)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, 128, 64)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 128, 64)), jnp.float32)
        for bq, bk in ((128, 128), (64, 64)):
            cases.append(("flash_attention", f"bq{bq}xbk{bk}",
                          ops.flash_attention, (q, k, v),
                          {"causal": True, "bq": bq, "bk": bk}))
    if "selective_scan" in kernels:
        # channel tiles are lane blocks: multiples of 128 on the TPU
        bs, t, i, s = 1, 64, 256, 16
        xi = jnp.asarray(rng.standard_normal((bs, t, i)), jnp.float32)
        dt = jnp.asarray(np.abs(rng.standard_normal((bs, t, i))) * 0.1,
                         jnp.float32)
        bm = jnp.asarray(rng.standard_normal((bs, t, s)), jnp.float32)
        cm = jnp.asarray(rng.standard_normal((bs, t, s)), jnp.float32)
        aa = jnp.asarray(-np.abs(rng.standard_normal((i, s))),
                         jnp.float32)
        h0 = jnp.zeros((bs, i, s), jnp.float32)
        for ct, ci in ((16, 128), (32, 256)):
            cases.append(("selective_scan", f"ct{ct}xci{ci}",
                          ops.selective_scan, (xi, dt, bm, cm, aa, h0),
                          {"ct": ct, "ci": ci}))
    if "strategy_eval" in kernels:
        from repro.core.ir import bert_large_workload
        from repro.core.macro import get_macro
        from repro.core.pruning import (
            DesignSpace,
            candidates_with_bw,
            enumerate_space,
        )
        cands = candidates_with_bw(enumerate_space(DesignSpace(
            mr=(1, 2), mc=(1, 2), scr=(1, 4), is_kb=(4, 64),
            os_kb=(4, 64))), 256)
        wl = bert_large_workload().merged().as_arrays()
        cases.append(("strategy_eval", "default", ops.strategy_eval,
                      (cands, wl, get_macro("vanilla-dcim")), {}))
    return cases


def run_microbench(kernels: typing.Sequence[str] | None = None,
                   repeats: int = 3, seed: int = 0,
                   ) -> list[MeasurementRecord]:
    """Time the real Pallas kernels over a small tiling sweep and return
    one :class:`MeasurementRecord` per (case, repeat).

    This is the measurement half of the two-fidelity calibration tier
    (``repro.core.calibration.fit_corrections`` fits correction factors
    from these records) and the shared body of ``repro-service profile``
    / ``calibrate``, the server's ``CIM_TUNER_PROFILE`` warm-up and
    ``benchmarks/run.py --profile-kernels`` -- tiny canonical shapes,
    interpret mode on CPU hosts.  Each case is warmed once (tracing +
    cost analysis) before the timed repeats, and the ``cim_kernel_*``
    registry families are populated as a side effect.  Enables
    ``CIM_TUNER_PROFILE`` for this process if unset."""
    if not profiling_enabled():
        os.environ[PROFILE_ENV] = "1"
    import jax

    import numpy as np

    kernels = tuple(kernels) if kernels else _ALL_KERNELS
    unknown = sorted(set(kernels) - set(_ALL_KERNELS))
    if unknown:
        raise ValueError(f"unknown kernels {unknown}; "
                         f"pick from {_ALL_KERNELS}")
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    records: list[MeasurementRecord] = []
    for kernel, tiling, fn, args, kwargs in _microbench_cases(kernels,
                                                              rng):
        bucket_fn = getattr(fn, "__bucket_fn__", None)
        bucket = bucket_fn(*args, **kwargs) if bucket_fn else tiling
        # warm-up: tracing/compile + one-time cost analysis stay out of
        # the timed repeats
        jax.block_until_ready(fn(*args, **kwargs))
        with _COST_LOCK:
            cost = _COST_CACHE.get((kernel, bucket))
        for _ in range(max(1, repeats)):
            t1 = time.perf_counter()
            jax.block_until_ready(fn(*args, **kwargs))
            records.append(MeasurementRecord(
                kernel=kernel, bucket=bucket, tiling=tiling,
                us=(time.perf_counter() - t1) * 1e6,
                flops=cost[0] if cost else None,
                bytes=cost[1] if cost else None, seed=seed))
    _M_RUNTIME.set(time.perf_counter() - t0)
    return records


# --------------------------------------------------------------------- #
# per-job measurement stash (engine -> queue -> store sidecar)
# --------------------------------------------------------------------- #
#: measured-fidelity runs park their records here keyed by job key; the
#: queue drains the stash into the result store's ``.measurements.json``
#: sidecar right before publishing the result (mirrors the timeline
#: recorder hand-off)
_MEASUREMENTS: dict[str, list[MeasurementRecord]] = {}
_MEAS_LOCK = threading.Lock()
_MEAS_CAP = 512


def record_measurements(key: str,
                        records: typing.Sequence[MeasurementRecord],
                        ) -> None:
    """Stash the measurement records backing one job's measured-fidelity
    re-score, keyed by the job's content address (bounded FIFO)."""
    with _MEAS_LOCK:
        if len(_MEASUREMENTS) >= _MEAS_CAP and key not in _MEASUREMENTS:
            _MEASUREMENTS.pop(next(iter(_MEASUREMENTS)))
        _MEASUREMENTS[key] = list(records)


def take_measurements(key: str) -> list[MeasurementRecord] | None:
    """Pop (and return) the stashed records for ``key``, or None."""
    with _MEAS_LOCK:
        return _MEASUREMENTS.pop(key, None)
