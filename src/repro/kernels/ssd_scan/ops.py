"""jit'd public wrapper for the SSD kernel (seq-major adapter)."""
from __future__ import annotations

import functools

import jax

from .kernel import ssd_scan_tpu


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a_log, b, c, d_skip, *, chunk: int = 128,
             interpret: bool = False):
    """Seq-major API matching repro.models.ssm.ssd_scan:
    x (s, bs, h, p); dt (s, bs, h); b/c (s, bs, g, n) -> (s, bs, h, p)."""
    xt = x.transpose(1, 2, 0, 3)
    dtt = dt.transpose(1, 2, 0)
    bt = b.transpose(1, 2, 0, 3)
    ct = c.transpose(1, 2, 0, 3)
    out = ssd_scan_tpu(xt, dtt, a_log, bt, ct, d_skip, chunk=chunk,
                       interpret=interpret)
    return out.transpose(2, 0, 1, 3)
