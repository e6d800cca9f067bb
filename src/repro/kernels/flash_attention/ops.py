"""jit'd public wrapper for the flash attention kernel.

The model stack's seq-major layout (s, b, h, dh) is adapted here; the
kernel itself works in (b, h, s, dh), the natural TPU tiling (last two
dims map to VMEM lanes/sublanes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import flash_attention_tpu


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_offset",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                    block_q=128, block_k=128, interpret=False):
    """Seq-major API: q (sq, b, hq, dh); k/v (skv, b, hkv, dh)."""
    qt = q.transpose(1, 2, 0, 3)
    kt = k.transpose(1, 2, 0, 3)
    vt = v.transpose(1, 2, 0, 3)
    out = flash_attention_tpu(qt, kt, vt, causal=causal, window=window,
                              q_offset=q_offset, block_q=block_q,
                              block_k=block_k, interpret=interpret)
    return out.transpose(2, 0, 1, 3)
