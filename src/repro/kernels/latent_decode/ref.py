"""Pure-jnp oracle for the latent decode kernel: the layer sliced out of
the cache twice, scores over all ``W`` columns and values over the first
``r``, every row read and those at or past ``live`` masked."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def latent_decode_ref(qc, lat_all, layer, live, *, rank: int):
    """qc (b, nq, W); lat_all (L, b, S, W) -> (num (b, nq, rank), m (b,
    nq), l (b, nq)), f32."""
    _, b, seq, width = lat_all.shape
    f32 = jnp.float32
    keys = jax.lax.dynamic_slice(lat_all, (layer, 0, 0, 0),
                                 (1, b, seq, width))[0]
    vals = jax.lax.dynamic_slice(lat_all, (layer, 0, 0, 0),
                                 (1, b, seq, rank))[0]
    valid = jnp.arange(seq, dtype=jnp.int32) < live
    sc = jnp.einsum("bhc,bsc->bhs", qc.astype(f32), keys.astype(f32))
    sc = jnp.where(valid[None, None, :], sc, NEG_INF)
    m = sc.max(axis=-1)
    p = jnp.where(valid[None, None, :], jnp.exp(sc - m[..., None]), 0.0)
    num = jnp.einsum("bhs,bsr->bhr", p, vals.astype(f32))
    return num, m, p.sum(axis=-1)
