"""jit'd public wrappers around the Pallas kernels.

``interpret=None`` picks the mode from the default JAX backend: the
compiled Mosaic kernels on a TPU, the Pallas interpreter on the CPU (where
the tests run), and an error on any other backend -- a kernel never runs
interpreted on an accelerator by accident.

With ``CIM_TUNER_PROFILE`` set, every call is timed to completion and
recorded into the ``cim_kernel_*`` metric families per (kernel, shape
bucket) -- see ``repro.obs.profile``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import cim_matmul as _cm
from repro.kernels import flash_attention as _fa
from repro.kernels import selective_scan as _ss
from repro.kernels import strategy_eval as _se
from repro.obs import profile as _profile


def _default_interpret() -> bool:
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for TPU and interpret on CPU only; the "
        f"default JAX backend is {platform!r}")


@partial(jax.jit, static_argnames=("tiling", "bm", "bn", "bk", "interpret"))
def _cim_matmul(a, b, *, tiling="AF", bm=_cm.DEFAULT_BM, bn=_cm.DEFAULT_BN,
                bk=_cm.DEFAULT_BK, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _cm.cim_matmul(a, b, tiling=tiling, bm=bm, bn=bn, bk=bk,
                          interpret=interpret)


@partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def _flash_attention(q, k, v, *, causal=True, bq=128, bk=128,
                     interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _fa.flash_attention(q, k, v, causal=causal, bq=bq, bk=bk,
                               interpret=interpret)


def _strategy_eval(candidates, ops_arr, macro, *, objective="ee",
                   interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    fn = partial(_se.strategy_eval, macro=macro, objective=objective,
                 interpret=interpret)
    return jax.jit(fn)(jnp.asarray(candidates, jnp.float32),
                       jnp.asarray(ops_arr, jnp.float32))


@partial(jax.jit, static_argnames=("ct", "ci", "interpret"))
def _selective_scan(xi, dt, bmat, cmat, a, h0, *, ct=_ss.DEFAULT_CT,
                    ci=_ss.DEFAULT_CI, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _ss.selective_scan(xi, dt, bmat, cmat, a, h0, ct=ct, ci=ci,
                              interpret=interpret)


# shape-bucket labels for the cim_kernel_* series (bounded cardinality:
# real callers reuse a handful of canonical shapes per kernel)
def _matmul_bucket(a, b, **kw):
    return f"{a.shape[0]}x{b.shape[1]}x{a.shape[1]}"


def _attn_bucket(q, k, v, **kw):
    return f"{q.shape[0]}x{q.shape[1]}x{k.shape[1]}x{q.shape[2]}"


def _strat_bucket(candidates, ops_arr, macro, **kw):
    return f"C{len(candidates)}xP{len(ops_arr)}"


def _scan_bucket(xi, dt, bmat, cmat, a, h0, **kw):
    return f"{xi.shape[0]}x{xi.shape[1]}x{xi.shape[2]}x{a.shape[1]}"


cim_matmul = _profile.instrument("cim_matmul", _cim_matmul,
                                 _matmul_bucket)
flash_attention = _profile.instrument("flash_attention", _flash_attention,
                                      _attn_bucket)
strategy_eval = _profile.instrument("strategy_eval", _strategy_eval,
                                    _strat_bucket)
selective_scan = _profile.instrument("selective_scan", _selective_scan,
                                     _scan_bucket)
