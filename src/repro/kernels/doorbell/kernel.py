"""Doorbell stage-copy Pallas kernel — row-blocked VMEM tiles.

Grid over row blocks of the (K, E) payload matrix; each step loads a
tile, applies the wire-dtype cast on the VPU (f32 -> bf16 when the
``wire_bf16`` attribute is on, identity otherwise), and writes the
staged tile.  The cast IS the copy: compression costs nothing beyond
the staging traffic the doorbell already pays (DESIGN.md §13).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _stage_copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...].astype(o_ref.dtype)


def stage_copy_tpu(x: jax.Array, *, wire_bf16: bool = False,
                   block_rows: int = 128, interpret: bool = False
                   ) -> jax.Array:
    """x (k, e) -> staged (k, e) in the wire dtype (bf16 when
    compressing an f32 burst, else x.dtype)."""
    k, e = x.shape
    out_dtype = (jnp.bfloat16 if wire_bf16 and x.dtype == jnp.float32
                 else x.dtype)
    block_rows = min(block_rows, k)
    while k % block_rows:
        block_rows //= 2
    block_rows = max(block_rows, 1)
    grid = (k // block_rows,)
    return pl.pallas_call(
        _stage_copy_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, e), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, e), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((k, e), out_dtype),
        interpret=interpret,
    )(x)
