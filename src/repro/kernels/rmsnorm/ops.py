"""jit'd public wrapper for the RMSNorm kernel."""
from __future__ import annotations

import functools

import jax

from .kernel import rmsnorm_tpu


@functools.partial(jax.jit,
                   static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, w, *, eps: float = 1e-6, block_rows: int = 256,
            interpret: bool = False):
    """x (..., d); w (d,)."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    out = rmsnorm_tpu(x2, w, eps=eps, block_rows=block_rows,
                      interpret=interpret)
    return out.reshape(shape)
