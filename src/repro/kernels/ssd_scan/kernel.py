"""Mamba2 SSD Pallas TPU kernel — chunked scan with VMEM-resident state.

Grid = (batch, heads, chunks) with the chunk dimension ``arbitrary``
(sequential): the (N, P) recurrent state lives in VMEM scratch across
chunk steps, so the inter-chunk recurrence never round-trips HBM — the
TPU-native replacement for the GPU kernel's shared-memory state.  Each
step does the intra-chunk quadratic part as (L×L)·(L×P) MXU matmuls.

Layout: x (b, h, s, p); dt (b, h, s); B/C (b, g, s, n); per-head A_log/D
(read as scalars from SMEM).
Chunk length L is the MXU tile (default 128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dtc_ref, dtr_ref, a_ref, b_ref, c_ref, d_ref, o_ref,
                state_scr, *, chunk: int):
    hi = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0].astype(jnp.float32)          # (L, P)
    dt_col = dtc_ref[0, 0].astype(jnp.float32)   # (L, 1)
    dt_row = dtr_ref[0, 0].astype(jnp.float32)   # (1, L)
    a = a_ref[hi]                                # scalar: -exp(A_log)
    b = b_ref[0, 0].astype(jnp.float32)          # (L, N)
    c = c_ref[0, 0].astype(jnp.float32)          # (L, N)
    d_skip = d_ref[hi]                           # scalar

    # cumulative log decay as a column and a row: masked reductions of the
    # (L, L) causal mask, so no 1-D relayout or cumsum is needed
    li = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = li >= lj
    cum_col = jnp.sum(jnp.where(causal, dt_row * a, 0.0), axis=1,
                      keepdims=True)             # (L, 1)
    cum_row = jnp.sum(jnp.where(li <= lj, dt_col * a, 0.0), axis=0,
                      keepdims=True)             # (1, L)
    cum_last = jnp.sum(dt_row * a, axis=1, keepdims=True)   # (1, 1)
    xbar = x * dt_col

    # intra-chunk: Y_diag[l] = Σ_{j<=l} (C_l·B_j) e^{cum_l-cum_j} xbar_j
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    m = jnp.where(causal, scores * jnp.exp(cum_col - cum_row), 0.0)
    y = jax.lax.dot_general(m, xbar, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # incoming state contribution: C_l · H_in · e^{cum_l}
    h_in = state_scr[...]                        # (N, P)
    y = y + jax.lax.dot_general(
        c * jnp.exp(cum_col), h_in, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    o_ref[0, 0] = (y + d_skip * x).astype(o_ref.dtype)

    # state update: H_out = e^{cum_last} H_in + Σ_j e^{cum_last-cum_j} B_j⊗xbar_j
    s_new = jax.lax.dot_general(
        b * jnp.exp(cum_last - cum_col), xbar, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)      # (N, P)
    state_scr[...] = jnp.exp(cum_last) * h_in + s_new


def ssd_scan_tpu(x, dt, a_log, b, c, d_skip, *, chunk: int = 128,
                 interpret: bool = False):
    """x (bs, h, s, p); dt (bs, h, s); a_log/d_skip (h,);
    b/c (bs, g, s, n).  Returns y (bs, h, s, p)."""
    bs, h, s, p = x.shape
    g, n = b.shape[1], b.shape[3]
    assert h % g == 0
    r = h // g
    chunk = min(chunk, s)
    while s % chunk:
        chunk //= 2
    n_chunks = s // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    grid = (bs, h, n_chunks)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    # dt rides twice, as a (chunk, 1) column and a (1, chunk) row tile:
    # each block's last two dims are then either whole or tile-aligned
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, 1),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, 1, chunk),
                         lambda bi, hi, ci: (bi, hi, 0, ci)),
            smem,
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci, r=r: (bi, hi // r, ci, 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci, r=r: (bi, hi // r, ci, 0)),
            smem,
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, p),
                               lambda bi, hi, ci: (bi, hi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((bs, h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(x, dt[..., None], dt[:, :, None, :],
      -jnp.exp(a_log.astype(jnp.float32)), b, c,
      d_skip.astype(jnp.float32))
