"""Latent attention decode Pallas TPU kernel: one read of each live block.

In the absorbed form of latent attention a decoded token's ``nq`` heads
all score against the same cached row ``[latent, rotary key]`` (``W``
wide, zero-padded to whole lanes), and the probabilities weigh the row's
first ``r`` columns, the latent.  This kernel computes, for one layer of
the whole latent cache ``(L, b, S, W)``, the partial flash triple over
the rows below ``live``: ``num (b, nq, r)``, ``m (b, nq)``, ``l (b, nq)``.

Grid ``(b / batch_tile, S / block)``, the row-block axis ``arbitrary``
and last, so (m, l, acc) stay in VMEM scratch across a tile's blocks.
The layer and ``live`` are scalar-prefetch operands.  The cache's index
map clamps the block index to the last block holding a live row, so a
dead block repeats the block before it and the pipeline copies nothing
for it; ``pl.when`` skips its compute.  A live block is copied from HBM
once: all ``W`` columns give the scores, the first ``r`` the values.
Rows at or past ``live`` are masked in both products, by ``where`` on
the values too, so a stale or non-finite row cannot leak through a zero
weight.  Only the last live block needs the masks; every block takes
them, which costs no measurable time and keeps one code path (the
kernel's machine code is loaded into device memory once per call site).
No live row leaves ``m`` at ``NEG_INF`` and ``l``, ``num`` at 0.

The products take the query and the probabilities in the cache's dtype
and accumulate in f32, which is what XLA's default precision does with
f32 operands on the TPU.
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
BLOCK_ROWS = 128       # cached rows a grid step reads
BATCH_TILE = 32        # batch rows a grid step reads


def _tile(n: int, want: int, align: int) -> int:
    """``want`` cut to a divisor of ``n`` on the tiling's ``align``, else
    all of ``n`` (a whole dim needs no alignment)."""
    t = math.gcd(n, want)
    return t if t % align == 0 else n


def block_rows(seq_len: int, want: int = BLOCK_ROWS) -> int:
    """Rows of a block of a ``seq_len``-row cache (bf16 sublane tiles)."""
    return _tile(seq_len, want, 16)


def _kernel(layer_ref, live_ref, q_ref, lat_ref, num_ref, m_ref, l_ref,
            m_scr, l_scr, acc_scr, *, block: int, rank: int, n_blocks: int):
    del layer_ref                      # used by the index map only
    ki = pl.program_id(1)
    start = ki * block
    live = live_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(start < live)
    def _accumulate():
        lat = lat_ref[0]                                   # (bt, block, W)
        q = q_ref[...].astype(lat.dtype)                   # (bt, nq, W)
        s = lax.dot_general(q, lat, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
        n = live - start
        s = jnp.where(lax.broadcasted_iota(jnp.int32, s.shape, 2) < n, s,
                      NEG_INF)
        vals = lat[..., :rank]                             # (bt, block, r)
        vals = jnp.where(lax.broadcasted_iota(jnp.int32, vals.shape, 1) < n,
                         vals, jnp.zeros_like(vals))
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
        acc_scr[...] = acc_scr[...] * corr[..., None] + lax.dot_general(
            p.astype(lat.dtype), vals, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == n_blocks - 1)
    def _out():
        num_ref[...] = acc_scr[...]
        m_ref[...] = m_scr[...]
        l_ref[...] = l_scr[...]


def latent_decode_tpu(qc, lat_all, layer, live, *, rank: int,
                      block: int = BLOCK_ROWS, batch_tile: int = BATCH_TILE,
                      interpret: bool = False):
    """qc (b, nq, W) f32; lat_all (L, b, S, W); layer, live () int32 ->
    (num (b, nq, rank), m (b, nq), l (b, nq)), f32, over the rows of
    layer ``layer`` below ``live``."""
    _, b, seq, width = lat_all.shape
    nq = qc.shape[1]
    block = block_rows(seq, block)
    bt = _tile(b, batch_tile, 8)
    n_blocks = seq // block
    f32 = jnp.float32

    def lat_map(bi, ki, layer_ref, live_ref):
        last = jnp.maximum(pl.cdiv(live_ref[0], block) - 1, 0)
        return layer_ref[0], bi, jnp.minimum(ki, last), 0

    isz = lat_all.dtype.itemsize
    lat_bytes = bt * block * width * isz
    vec = bt * nq * (width + rank + 2) * 4            # q, acc, m, l
    # double-buffered blocks and outputs, scratch, the step's temporaries
    vmem = 3 * lat_bytes + 3 * vec + 4 * bt * nq * block * 4
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b // bt, n_blocks),
        in_specs=[
            pl.BlockSpec((bt, nq, width), lambda bi, ki, *_: (bi, 0, 0)),
            pl.BlockSpec((1, bt, block, width), lat_map),
        ],
        out_specs=[
            pl.BlockSpec((bt, nq, rank), lambda bi, ki, *_: (bi, 0, 0)),
            pl.BlockSpec((bt, nq), lambda bi, ki, *_: (bi, 0)),
            pl.BlockSpec((bt, nq), lambda bi, ki, *_: (bi, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bt, nq), f32),                 # running max
            pltpu.VMEM((bt, nq), f32),                 # running exp-sum
            pltpu.VMEM((bt, nq, rank), f32),           # accumulator
        ])
    return pl.pallas_call(
        functools.partial(_kernel, block=block, rank=rank,
                          n_blocks=n_blocks),
        grid_spec=grid_spec,
        name="latent_decode",
        out_shape=[jax.ShapeDtypeStruct((b, nq, rank), f32),
                   jax.ShapeDtypeStruct((b, nq), f32),
                   jax.ShapeDtypeStruct((b, nq), f32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(32 * 2**20, vmem + 4 * 2**20)),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      jnp.reshape(live, (1,)).astype(jnp.int32), qc.astype(f32), lat_all)
