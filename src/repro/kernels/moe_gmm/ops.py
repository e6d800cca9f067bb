"""jit'd public wrapper for the MoE grouped matmul."""
from __future__ import annotations

import functools

import jax

from .kernel import moe_gmm_tpu


@functools.partial(jax.jit, static_argnames=("act", "block_c", "interpret"))
def moe_gmm(x, w1, w2, *, act: str = "swiglu", block_c: int = 128,
            interpret: bool = False):
    return moe_gmm_tpu(x, w1, w2, act=act, block_c=block_c,
                       interpret=interpret)
