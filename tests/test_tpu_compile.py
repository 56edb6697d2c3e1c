"""Compile-only checks against a described TPU v5e (topology ``v5e:2x2``):
the four Pallas kernels at the widths ``chip_smoke.py`` runs them at, the
kernel profiler's microbench cases, and the engine's exhaustive executable
at the Fig. 7 buckets.  Nothing runs; the TPU compiler refuses here what
it would refuse on the chip.  Tests skip where no topology can be
described (no TPU compiler installed)."""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 -- no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


def test_cim_matmul_compiles(one_chip):
    from repro.kernels import cim_matmul as cm

    x = _spec(one_chip, (1024, 1024), jnp.bfloat16)
    for tiling in ("AF", "PF"):
        fn = functools.partial(cm.cim_matmul, tiling=tiling)
        assert "tpu_custom_call" in _compile(fn, x, x)


def test_flash_attention_compiles(one_chip):
    from repro.kernels import flash_attention as fa

    q = _spec(one_chip, (8, 1024, 128), jnp.bfloat16)
    assert "tpu_custom_call" in _compile(fa.flash_attention, q, q, q)


def test_selective_scan_compiles(one_chip):
    """falcon-mamba-7b's width: d_inner 8192, d_state 16, 512 steps."""
    from repro.kernels import selective_scan as ss

    b, t, i, s = 1, 512, 8192, 16
    args = [_spec(one_chip, shape) for shape in (
        (b, t, i), (b, t, i), (b, t, s), (b, t, s), (i, s), (b, i, s))]
    assert "tpu_custom_call" in _compile(ss.selective_scan, *args)


def test_strategy_eval_compiles(one_chip):
    """One exhaustive chunk of candidates x bert-large's merged ops."""
    from repro.core.ir import bert_large_workload
    from repro.core.macro import get_macro
    from repro.kernels import strategy_eval as se

    ops = bert_large_workload().merged().as_arrays()
    fn = functools.partial(se.strategy_eval,
                           macro=get_macro("vanilla-dcim"))
    assert "tpu_custom_call" in _compile(
        fn, _spec(one_chip, (4096, 6)), _spec(one_chip, ops.shape))


@pytest.mark.parametrize("kernel", ["cim_matmul", "flash_attention",
                                    "selective_scan", "strategy_eval"])
def test_microbench_cases_compile(one_chip, kernel):
    """The profiler's sweep (``repro-service profile``/``calibrate``, the
    serve warm-up) compiles on the chip, every tiling variant."""
    from repro.obs.profile import _microbench_cases

    cases = _microbench_cases((kernel,), np.random.default_rng(0))
    assert cases
    for _name, _tiling, fn, args, kwargs in cases:
        jitted = fn.__wrapped__
        arrays = [a for a in args if hasattr(a, "shape")]
        static = [a for a in args if not hasattr(a, "shape")]

        def call(*xs, _f=jitted, _static=tuple(static), _kw=kwargs):
            return _f(*xs, *_static, **_kw, interpret=False)

        specs = [_spec(one_chip, a.shape, a.dtype) for a in arrays]
        assert "tpu_custom_call" in _compile(call, *specs)


@pytest.mark.parametrize("ops_pad,jobs", [(8, 24), (16, 4)])
def test_exhaustive_executable_compiles(one_chip, ops_pad, jobs):
    """The engine's exhaustive executable at the Fig. 7 buckets: six of
    the seven networks (bert-large's bucket) pad to 8 merged ops, 24 jobs;
    whisper-small pads to 16, 4 jobs; one [jobs, 4096, 6] chunk."""
    from repro.core.engine import (
        ExplorationEngine,
        ExploreJob,
        _job_arrays,
        _stack_jobs,
    )
    from repro.core.ir import bert_large_workload
    from repro.core.macro import get_macro

    engine = ExplorationEngine(persistent_compile_cache=False)
    job = ExploreJob(get_macro("vanilla-dcim"), bert_large_workload(), 5.0)
    p = engine._prepare(job)._replace(ops_pad=ops_pad)
    stacked = _stack_jobs([_job_arrays(p)] * jobs)
    specs = jax.tree.map(
        lambda a: _spec(one_chip, a.shape,
                        jax.dtypes.canonicalize_dtype(a.dtype)), stacked)
    block = _spec(one_chip, (jobs, engine.EXHAUSTIVE_CHUNK, 6))
    fn = engine._exhaustive_executable(ops_pad).__wrapped__
    compiled = fn.lower(specs, block).compile()
    assert compiled.as_text()


@pytest.mark.parametrize("ops_pad", [8, 16])
def test_finish_executable_compiles(one_chip, ops_pad):
    """The engine's result epilogue at the Fig. 7 buckets: one job's
    packed row, the shape ``_finish`` calls it with."""
    from repro.core.engine import ExplorationEngine, ExploreJob, _finish_row
    from repro.core.ir import bert_large_workload
    from repro.core.macro import get_macro
    from repro.core.template import AcceleratorConfig

    engine = ExplorationEngine(persistent_compile_cache=False)
    job = ExploreJob(get_macro("vanilla-dcim"), bert_large_workload(), 5.0)
    p = engine._prepare(job)._replace(ops_pad=ops_pad)
    row = _finish_row(p, AcceleratorConfig(1, 1, 1, 2, 2, bw=256))
    fn = engine._finish_executable(ops_pad).__wrapped__
    compiled = fn.lower(_spec(one_chip, row.shape)).compile()
    assert "jit_finish_metrics" in compiled.as_text()
