"""Batched CIM-Tuner cost-model evaluation as a Pallas VPU kernel.

The DSE hot loop evaluates candidates x operators x 8 strategies of pure
elementwise arithmetic -- bandwidth-light, VPU-bound.  This kernel puts the
candidate axis on the TPU's lanes: each grid step holds a ``[6, T]`` block
of candidate columns, and every operator's cost table is one ``[8, T]``
array (strategies on sublanes) built by the same closed-form
``core.cost_model.matmul_cost`` the engine vmaps.  Operators are read as
scalars from SMEM inside a ``fori_loop``; the per-operator strategy argmin
and the count-weighted sums run as sublane reductions.  ``kernels/ref.py``
checks it against the engine's own objective (``make_objective_fn``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import cost_model
from repro.core.calibration import resolve_tech
from repro.core.macro import MacroSpec
from repro.core.strategies import ALL_STRATEGIES, STRATEGY_SETS

#: candidates per grid step (the lane axis; a multiple of 128)
CAND_TILE = 512


def _per_strategy(strat, values):
    """``[8, 1]`` column holding ``values[s]`` in row ``s`` (built from an
    iota: Pallas kernels may not capture array constants)."""
    out = jnp.zeros(strat.shape, jnp.float32)
    for s, v in enumerate(values):
        out = jnp.where(strat == s, jnp.float32(v), out)
    return out


def _objective_block(cfg, n_ops, op_at, macro, tech, objective,
                     strategy_set):
    """Best-strategy objective of a candidate block.

    ``cfg`` is the six config rows (mr, mc, scr, is_kb, os_kb, bw), each
    ``[1, T]``; ``op_at(p)`` returns operator ``p``'s (m, k, n, count)
    scalars.  Returns the ``[1, T]`` objective values."""
    mp, tp = cost_model._as_params(macro, tech)
    code = cost_model.objective_code(objective)
    area = cost_model.area_mm2_jnp(cfg, mp, tp)
    n_strat = len(ALL_STRATEGIES)
    strat = jax.lax.broadcasted_iota(jnp.int32, (n_strat, 1), 0)
    rev = _per_strategy(strat, [s.spatial == "R" for s in ALL_STRATEGIES])
    wp = _per_strategy(strat, [s.temporal == "WP" for s in ALL_STRATEGIES])
    pf = _per_strategy(strat, [s.tiling == "PF" for s in ALL_STRATEGIES])
    allowed = _per_strategy(
        strat, [s in STRATEGY_SETS[strategy_set] for s in ALL_STRATEGIES])

    def add_op(p, acc):
        m, k, n, count = op_at(p)
        tbl = cost_model.matmul_cost(m, k, n, rev, wp, pf, *cfg, area,
                                     mp, tp)
        lat = jnp.where(allowed > 0, tbl.latency_cycles,
                        cost_model.INFEASIBLE)                  # [8, T]
        en = jnp.where(allowed > 0, tbl.energy_pj, cost_model.INFEASIBLE)
        score = cost_model._score(lat, en, code)
        best = jnp.min(score, axis=0, keepdims=True)
        idx = jnp.min(jnp.where(score == best, strat, n_strat), axis=0,
                      keepdims=True)
        pick = strat == idx
        return (acc[0] + jnp.sum(jnp.where(pick, lat, 0.0), axis=0,
                                 keepdims=True) * count,
                acc[1] + jnp.sum(jnp.where(pick, en, 0.0), axis=0,
                                 keepdims=True) * count)

    zeros = jnp.zeros_like(area)
    lat, en = jax.lax.fori_loop(0, n_ops, add_op, (zeros, zeros))
    val = cost_model.objective_value(lat, en, code)
    return jnp.where(cost_model.bandwidth_ok_jnp(cfg, mp), val,
                     cost_model.INFEASIBLE)


def _kernel(cfg_ref, ops_ref, o_ref, *, n_ops, macro, tech, objective,
            strategy_set):
    cfg = [cfg_ref[i:i + 1, :] for i in range(6)]

    def op_at(p):
        return tuple(ops_ref[p, c] for c in range(4))

    o_ref[...] = _objective_block(cfg, n_ops, op_at, macro, tech,
                                  objective, strategy_set)


def strategy_eval(
    candidates: jax.Array,      # [C, 6] (mr, mc, scr, is_kb, os_kb, bw)
    ops_arr: jax.Array,         # [P, 5]
    macro: MacroSpec,
    *,
    objective: str = "ee",
    strategy_set: str = "st",
    tech=None,
    tile: int = CAND_TILE,
    interpret: bool = False,
) -> jax.Array:
    """``[C]`` best-strategy objective of every candidate (no area
    penalty; bandwidth-infeasible rows are ``INFEASIBLE``)."""
    tech = resolve_tech(tech)
    c = candidates.shape[0]
    pad = (-c) % tile
    cand_t = jnp.pad(candidates.astype(jnp.float32), ((0, pad), (0, 0)),
                     constant_values=1.0).T                 # [6, C + pad]
    out = pl.pallas_call(
        functools.partial(_kernel, n_ops=ops_arr.shape[0], macro=macro,
                          tech=tech, objective=objective,
                          strategy_set=strategy_set),
        grid=(cand_t.shape[1] // tile,),
        in_specs=[
            pl.BlockSpec((6, tile), lambda i: (0, i)),
            pl.BlockSpec(memory_space=pltpu.SMEM),          # whole, scalar
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, cand_t.shape[1]), jnp.float32),
        interpret=interpret,
    )(cand_t, ops_arr.astype(jnp.float32))
    return out[0, :c]
