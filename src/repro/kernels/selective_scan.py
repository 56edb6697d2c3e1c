"""Fused selective-scan (Mamba-1) Pallas kernel — the TPU-native answer to
the roofline finding that mamba prefill/train is bound by materializing
[B, T, I, S] recurrence coefficients in HBM (EXPERIMENTS Perf cell B).

Layout: grid (B, I_tiles, T_chunks), T innermost.  The hidden state is
held transposed, h [S, I_TILE] with channels on the lanes, in VMEM scratch
for the *entire* sequence of one (batch, channel-tile): coefficients
da = exp(dt*a) and dbx = dt*B*x are computed on the fly from the
[CT, I_TILE] / [CT, S] chunk inputs and never touch HBM.  HBM traffic is
exactly inputs (xi, dt, b, c) + outputs (y) -- the information-theoretic
minimum -- versus the jnp path's O(T*I*S)-per-level associative-scan
materializations.

The recurrence is sequential over time inside the chunk (lax.fori_loop on
[S, I_TILE] VPU ops).  Each step reads its timestep's rows straight from
the input refs and writes its output row into the output ref (``pl.ds``);
the B and C rows turn into [S, 1] columns by a masked lane reduction, so
no transpose runs in the kernel.  TPU grid steps along the last axis are
sequential, so the scratch legally carries state across T-chunks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_CT = 128       # timesteps per grid step
DEFAULT_CI = 256       # channel tile


def _column(row, eye):
    """[1, S] row -> [S, 1] column (masked lane reduction)."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _kernel(xi_ref, dt_ref, b_ref, c_ref, a_ref, h0_ref,
            y_ref, hlast_ref, h_ref, *, n_tchunks: int, ct: int):
    t_step = pl.program_id(2)

    @pl.when(t_step == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)       # [S, CI]

    a = a_ref[...].astype(jnp.float32)                   # [S, CI]
    s = a.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (s, s), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (s, s), 1))

    def step(t, h):
        row = pl.ds(t, 1)
        dt = dt_ref[0, row, :].astype(jnp.float32)       # [1, CI]
        xi = xi_ref[0, row, :].astype(jnp.float32)       # [1, CI]
        bm = _column(b_ref[0, row, :].astype(jnp.float32), eye)  # [S, 1]
        cm = _column(c_ref[0, row, :].astype(jnp.float32), eye)  # [S, 1]
        h = jnp.exp(dt * a) * h + bm * (dt * xi)         # [S, CI]
        y_ref[0, row, :] = jnp.sum(h * cm, axis=0,
                                   keepdims=True).astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, ct, step, h_ref[...])
    h_ref[...] = h

    @pl.when(t_step == n_tchunks - 1)
    def _emit_state():
        hlast_ref[0, ...] = h.astype(hlast_ref.dtype)


def selective_scan(
    xi: jax.Array,       # [B, T, I]
    dt: jax.Array,       # [B, T, I]
    bmat: jax.Array,     # [B, T, S]
    cmat: jax.Array,     # [B, T, S]
    a: jax.Array,        # [I, S]
    h0: jax.Array,       # [B, I, S]
    *,
    ct: int = DEFAULT_CT,
    ci: int = DEFAULT_CI,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y [B, T, I], h_last [B, I, S])."""
    b, t, i = xi.shape
    s = a.shape[1]
    ci = min(ci, i)
    pad_t = (-t) % ct
    pad_i = (-i) % ci
    if pad_t:
        # dt = 0 padding makes the extra steps identity (da=1, dbx=0)
        xi = jnp.pad(xi, ((0, 0), (0, pad_t), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad_t), (0, 0)))
        bmat = jnp.pad(bmat, ((0, 0), (0, pad_t), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, pad_t), (0, 0)))
    if pad_i:
        xi = jnp.pad(xi, ((0, 0), (0, 0), (0, pad_i)))
        dt = jnp.pad(dt, ((0, 0), (0, 0), (0, pad_i)))
        a = jnp.pad(a, ((0, pad_i), (0, 0)))
        h0 = jnp.pad(h0, ((0, 0), (0, pad_i), (0, 0)))
    tp, ip = xi.shape[1], xi.shape[2]
    grid = (b, ip // ci, tp // ct)

    y, hlast = pl.pallas_call(
        functools.partial(_kernel, n_tchunks=tp // ct, ct=ct),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, ct, ci), lambda bb, ii, tt: (bb, tt, ii)),  # xi
            pl.BlockSpec((1, ct, ci), lambda bb, ii, tt: (bb, tt, ii)),  # dt
            pl.BlockSpec((1, ct, s), lambda bb, ii, tt: (bb, tt, 0)),    # b
            pl.BlockSpec((1, ct, s), lambda bb, ii, tt: (bb, tt, 0)),    # c
            pl.BlockSpec((s, ci), lambda bb, ii, tt: (0, ii)),           # a^T
            pl.BlockSpec((1, s, ci), lambda bb, ii, tt: (bb, 0, ii)),    # h0^T
        ],
        out_specs=[
            pl.BlockSpec((1, ct, ci), lambda bb, ii, tt: (bb, tt, ii)),  # y
            pl.BlockSpec((1, s, ci), lambda bb, ii, tt: (bb, 0, ii)),    # h^T
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, tp, ip), xi.dtype),
            jax.ShapeDtypeStruct((b, s, ip), h0.dtype),
        ],
        scratch_shapes=[_vmem((s, ci), jnp.float32)],
        interpret=interpret,
    )(xi, dt, bmat, cmat, a.T, jnp.swapaxes(h0, 1, 2))
    return y[:, :t, :i], jnp.swapaxes(hlast, 1, 2)[:, :i]


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)
