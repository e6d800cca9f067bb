"""jit'd public wrappers for the doorbell stage-copy (DESIGN.md §13)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.packet_pool import pool_get_copy_n
from .kernel import stage_copy_tpu
from .ref import _rows_to_bytes


@functools.partial(jax.jit, static_argnames=("wire_bf16", "interpret"))
def stage_copy(payloads: jax.Array, *, wire_bf16: bool = False,
               interpret: bool = False) -> jax.Array:
    """(k, e) payloads -> (k, row_bytes) packed uint8 wire image, one
    dispatch: the Pallas tile copy applies the wire-dtype cast and the
    byte view is a free bitcast on the staged result.  ``interpret``
    runs the kernel in the Pallas interpreter (off the TPU)."""
    staged = stage_copy_tpu(payloads, wire_bf16=wire_bf16,
                            interpret=interpret)
    return _rows_to_bytes(staged)


@functools.partial(jax.jit, static_argnames=("wire_bf16", "interpret"))
def stage_copy_push(pool, buf, lane, payloads, steal_seed, *,
                    wire_bf16: bool = False, interpret: bool = False):
    """The fused stage-copy-push: ONE dispatch stages the doorbell's
    payloads into wire bytes (bf16-compressing when asked), pops a burst
    of packet slots, and scatters the wire rows into the pool's backing
    buffers.  Returns ``(pool', buf', ids, got, status)`` with
    :func:`repro.core.packet_pool.pool_get_copy_n`'s contract — on a
    short grab only the allocated prefix is written."""
    staged = stage_copy_tpu(payloads, wire_bf16=wire_bf16,
                            interpret=interpret)
    rows = _rows_to_bytes(staged)
    return pool_get_copy_n(pool, buf, lane, rows, steal_seed)
