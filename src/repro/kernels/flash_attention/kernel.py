"""Flash attention Pallas TPU kernel — explicit VMEM tiling.

TPU adaptation of the flash recurrence (DESIGN/HW-adaptation): the KV loop
is a *grid dimension* with ``arbitrary`` semantics, so Mosaic keeps the
(m, l, acc) state resident in VMEM scratch across KV steps while the MXU
consumes (block_q × dh)·(dh × block_k) tiles; q/k/v blocks stream
HBM→VMEM via BlockSpecs.  Block shapes default to MXU-aligned
(128, 128)·dh multiples.

Layout: q (b, hq, sq, dh); k/v (b, hkv, skv, dh); GQA via per-q-head kv
index mapping (hq % hkv == 0).  Causal and sliding-window masks are
applied from global positions (``q_offset`` supports SP-local q).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, causal: bool, window: int, q_offset: int,
                 block_q: int, block_k: int, n_k: int):
    qi = pl.program_id(2)              # q-block index ("parallel")
    ki = pl.program_id(3)              # kv-block index ("arbitrary": last)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, dh)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, dh)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)
    q_pos = q_offset + qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-37)[:, None]
                       ).astype(o_ref.dtype)


def flash_attention_tpu(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        q_offset: int = 0, block_q: int = 128,
                        block_k: int = 128,
                        interpret: bool = False) -> jax.Array:
    """q (b, hq, sq, dh); k/v (b, hkv, skv, dh) -> (b, hq, sq, dh)."""
    b, hq, sq, dh = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0
    g = hq // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    while sq % block_q:
        block_q //= 2
    while skv % block_k:
        block_k //= 2
    n_q, n_k = sq // block_q, skv // block_k
    scale = 1.0 / math.sqrt(dh)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, block_q=block_q, block_k=block_k, n_k=n_k)

    grid = (b, hq, n_q, n_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda bi, hi, qi, ki, g=g: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda bi, hi, qi, ki, g=g: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),       # running max
            pltpu.VMEM((block_q,), jnp.float32),       # running exp-sum
            pltpu.VMEM((block_q, dh), jnp.float32),    # accumulator
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
    )(q, k, v)
