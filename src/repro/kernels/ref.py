"""Pure-jnp oracles for every kernel (the correctness ground truth)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.calibration import resolve_tech


def matmul_ref(a: jax.Array, b: jax.Array, out_dtype=None) -> jax.Array:
    out_dtype = out_dtype or a.dtype
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                   preferred_element_type=jnp.float32).astype(out_dtype)


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True) -> jax.Array:
    """Naive softmax attention.  q,k,v: [BH, T|S, d]."""
    d = q.shape[-1]
    s = jnp.einsum("btd,bsd->bts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(d)
    if causal:
        t, s_len = s.shape[-2], s.shape[-1]
        mask = jnp.arange(s_len)[None, :] <= jnp.arange(t)[:, None]
        s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bts,bsd->btd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def strategy_eval_ref(candidates, ops_arr, macro, *, objective="ee",
                      strategy_set="st", tech=None):
    """The engine's own objective (no area budget), vmapped over the
    candidates."""
    from repro.core import cost_model
    fn = cost_model.make_objective_fn(
        jnp.asarray(ops_arr, jnp.float32), macro, resolve_tech(tech),
        objective, strategy_set)
    return jax.vmap(fn)(jnp.asarray(candidates, jnp.float32))


def selective_scan_ref(xi, dt, bmat, cmat, a, h0, chunk: int = 64):
    """Oracle via the model's chunked associative linear scan."""
    from repro.models.ssm import linear_scan
    da = jnp.exp(dt[..., None] * a[None, None])
    dbx = (dt * xi)[..., None] * bmat[:, :, None, :]
    hs = jax.vmap(lambda aa, bb, h: linear_scan(aa, bb, h, chunk=chunk))(
        da, dbx, h0)
    y = jnp.einsum("btis,bts->bti", hs, cmat)
    return y, hs[:, -1]
