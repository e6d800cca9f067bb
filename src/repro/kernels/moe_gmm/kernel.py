"""MoE grouped matmul Pallas TPU kernel.

Computes out[e] = act(x[e] @ w1[e]) @ w2[e] block-by-block: grid =
(experts, capacity blocks, f blocks); per step the (block_c, d) token tile
and one (d, BLOCK_F) / (BLOCK_F, d) slice of the expert's weights stream
into VMEM, two MXU matmuls produce a partial tile, and an f32 VMEM
accumulator sums the partials over the ``arbitrary`` f axis.  Blocking f
keeps a step inside scoped VMEM at real expert widths (d=2048, f=1024).
The activated intermediate stays f32 into the second matmul.
This fuses the expert FFN so dispatched tokens make one HBM round trip
instead of three (the packet-pool slots are read once, written once).

``act``: 'swiglu' expects w1 = [gate|up] fused on the output dim (the
kernel reads the gate and up slices of an f block as two BlockSpecs
over the same array).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_F = 256          # f columns of w1 (rows of w2) per grid step


def _act(act: str, h, up):
    if act == "swiglu":
        return jax.nn.silu(h) * up
    if act == "geglu":
        return jax.nn.gelu(h, approximate=True) * up
    if act == "gelu":
        return jax.nn.gelu(h, approximate=True)
    return jnp.square(jax.nn.relu(h))                # relu2


def _gmm_kernel(*refs, act: str, gated: bool, n_f: int):
    if gated:
        x_ref, wg_ref, wu_ref, w2_ref, o_ref, acc_ref = refs
    else:
        x_ref, wg_ref, w2_ref, o_ref, acc_ref = refs
    fi = pl.program_id(2)              # f-block index ("arbitrary": last)

    @pl.when(fi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]                                     # (bc, d)
    dims = (((1,), (0,)), ((), ()))
    h = jax.lax.dot_general(x, wg_ref[0], dims,
                            preferred_element_type=jnp.float32)  # (bc, bf)
    up = (jax.lax.dot_general(x, wu_ref[0], dims,
                              preferred_element_type=jnp.float32)
          if gated else None)
    h = _act(act, h, up)
    w2 = w2_ref[0].astype(jnp.float32)               # (bf, d)
    acc_ref[...] += jax.lax.dot_general(h, w2, dims,
                                        preferred_element_type=jnp.float32)

    @pl.when(fi == n_f - 1)
    def _finalize():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def moe_gmm_tpu(x, w1, w2, *, act: str = "swiglu", block_c: int = 128,
                interpret: bool = False):
    """x (E, C, d); w1 (E, d, m·f); w2 (E, f, d) -> (E, C, d)."""
    e, cap, d = x.shape
    f = w2.shape[1]
    gated = act in ("swiglu", "geglu")
    block_c = min(block_c, cap)
    while cap % block_c:
        block_c //= 2
    block_f = min(BLOCK_F, f)
    while f % block_f:
        block_f //= 2
    n_f = f // block_f
    grid = (e, cap // block_c, n_f)
    w1_specs = [pl.BlockSpec((1, d, block_f), lambda ei, ci, fi: (ei, 0, fi))]
    if gated:                          # the up half sits n_f blocks later
        w1_specs.append(pl.BlockSpec(
            (1, d, block_f), lambda ei, ci, fi, n_f=n_f: (ei, 0, n_f + fi)))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, act=act, gated=gated, n_f=n_f),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c, d), lambda ei, ci, fi: (ei, ci, 0)),
            *w1_specs,
            pl.BlockSpec((1, block_f, d), lambda ei, ci, fi: (ei, fi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_c, d),
                               lambda ei, ci, fi: (ei, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((e, cap, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, d), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(x, *([w1] * len(w1_specs)), w2)
