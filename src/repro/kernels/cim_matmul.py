"""AF/PF blocked matmul: the paper's macro-level tiling on TPU.

The CIM macro's Accumulation-First vs Parallel-First choice (paper Fig. 6) is
exactly the loop-order choice of a blocked matmul:

  AF  -- grid (m, n, k), K innermost: one output tile stays in the VMEM
         accumulator while SCR consecutive K-blocks stream through (psum
         register reuse); input blocks are re-fetched per output column.
  PF  -- grid (m, k, n), N innermost: one input block stays VMEM-resident
         while SCR consecutive N-blocks compute (input reuse); the output
         tile is revisited across the K grid axis, so partial sums make
         extra HBM round-trips -- the Output-SRAM pressure of the paper.

Both orders produce identical numerics (tests assert allclose against the
jnp.dot oracle across shape/dtype sweeps); they differ in traffic, which is
what CIM-Tuner's cost model trades off.  Block shapes are MXU-aligned
(multiples of 128 on the matmul dims).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 128


def _kernel_af(a_ref, b_ref, o_ref, acc_ref, *, n_contract: int):
    """AF body: K innermost; the f32 VMEM scratch plays the CIM psum
    register -- one output tile accumulates fully before a single HBM emit."""
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...].astype(jnp.float32),
        b_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(step == n_contract - 1)
    def _emit():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _kernel_pf(a_ref, b_ref, o_ref, psum_ref):
    """PF body: N innermost; the input block stays VMEM-resident while the
    output tiles of its row are revisited across the K grid axis.  The
    running partial sums live in a VMEM row panel (``psum_ref``, one tile
    per N block -- the TPU never reads an output block back from HBM), and
    every step writes its tile's partial sum out: the psum HBM traffic
    CIM-Tuner charges the PF strategy (paper Fig. 8).  Accumulation happens
    at the output dtype, mirroring dw_psum."""
    step = pl.program_id(1)
    j = pl.program_id(2)
    partial_ = jnp.dot(
        a_ref[...].astype(jnp.float32),
        b_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)

    @pl.when(step == 0)
    def _init():
        psum_ref[j] = partial_

    @pl.when(step > 0)
    def _accumulate():
        psum_ref[j] = psum_ref[j] + partial_

    o_ref[...] = psum_ref[j]


def cim_matmul(
    a: jax.Array,              # [M, K]
    b: jax.Array,              # [K, N]
    *,
    tiling: str = "AF",        # "AF" | "PF"
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype

    pm, pk, pn = (-m) % bm, (-k) % bk, (-n) % bn
    if pm or pk:
        a = jnp.pad(a, ((0, pm), (0, pk)))
    if pk or pn:
        b = jnp.pad(b, ((0, pk), (0, pn)))
    gm, gk, gn = a.shape[0] // bm, a.shape[1] // bk, b.shape[1] // bn

    if tiling == "AF":
        grid = (gm, gn, gk)                  # K innermost: psum reuse
        out = pl.pallas_call(
            functools.partial(_kernel_af, n_contract=gk),
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
                pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
            out_shape=jax.ShapeDtypeStruct(
                (a.shape[0], b.shape[1]), out_dtype),
            scratch_shapes=[_vmem_scratch((bm, bn), jnp.float32)],
            interpret=interpret,
        )(a, b)
    elif tiling == "PF":
        grid = (gm, gk, gn)                  # N innermost: input reuse
        out = pl.pallas_call(
            _kernel_pf,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, s, j: (i, s)),
                pl.BlockSpec((bk, bn), lambda i, s, j: (s, j)),
            ],
            # output revisited across the K grid axis: psum traffic
            out_specs=pl.BlockSpec((bm, bn), lambda i, s, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct(
                (a.shape[0], b.shape[1]), out_dtype),
            scratch_shapes=[_vmem_scratch((gn, bm, bn), out_dtype)],
            interpret=interpret,
        )(a, b)
    else:
        raise ValueError(f"tiling must be AF or PF, got {tiling!r}")
    return out[:m, :n]


def _vmem_scratch(shape, dtype):
    """VMEM accumulator (the psum register of the CIM analogy)."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)
