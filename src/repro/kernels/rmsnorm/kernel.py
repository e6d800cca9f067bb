"""RMSNorm Pallas TPU kernel — row-blocked VMEM tiles, fp32 statistics.

Grid over row blocks; each step loads a (block_rows, d) tile, computes the
per-row mean square in fp32 on the VPU, and writes the scaled tile.  d is
kept whole per tile (norm reductions are over the full feature dim; for
the assigned archs d ≤ 12288 → ≤ 3 MiB bf16 per tile, comfortably VMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    o_ref[...] = (y * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_tpu(x: jax.Array, w: jax.Array, *, eps: float = 1e-6,
                block_rows: int = 256, interpret: bool = False) -> jax.Array:
    """x (rows, d); w (d,) -> (rows, d)."""
    rows, d = x.shape
    block_rows = min(block_rows, rows)
    while rows % block_rows:
        block_rows //= 2
    block_rows = max(block_rows, 1)
    grid = (rows // block_rows,)
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret,
    )(x, w)
