"""In-graph collectives — LCI-X's zero-copy protocol on the ICI torus.

This module is the heart of the TPU adaptation (DESIGN.md §2).  Every
function takes a ``CommConfig`` whose mode selects between:

* ``BSP``           — monolithic XLA collective, compute strictly after
  (paper's MPI/bulk-synchronous baseline);
* ``LCI_SHARED``    — ring decomposition on a single channel: per-step
  ``ppermute`` is asynchronous (``collective-permute-start/done``) so XLA
  can overlap the *next* transfer with the *current* compute chunk;
* ``LCI_DEDICATED`` — ring decomposition over dedicated channels: the two
  ICI link directions run counter-rotating rings concurrently, halving the
  number of serial ring steps (gather: distance-split; reduce: payload-
  split), on top of the same per-step overlap.

All functions must be called inside ``shard_map`` with ``axis_name`` bound.
Matmul accumulation is fp32 (``preferred_element_type``) regardless of the
payload dtype.  Ring loops are written so that *no wasted ppermute* is
emitted (first/last iterations peeled); the dry-run's collective-byte count
is therefore exact, and no collective sits under a ``lax.cond``.

Also here: the collective primitives the paper says LCI provides (§6
"dissemination-based barrier and tree-based broadcast/reduce") built on the
same ppermute substrate.

Correctness invariants (tested in tests/test_collectives.py against the BSP
mode and pure-jnp oracles):

* gather rings: the forward ring delivers sources ``idx-1 .. idx-sf``
  (``sf = ceil((P-1)/2)``), the backward ring ``idx+1 .. idx+sb``
  (``sb = P-1-sf``) — a partition of the non-self sources, each carried the
  short way round the torus.
* reduce rings: a contribution added at rank ``r`` on step ``i`` rides the
  +1 ring ``P-1-i`` more hops, so it must target ``dst = r + P-1-i``; on
  the −1 ring, ``dst = r + i + 1``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from jax.lax import axis_size

from .modes import CommConfig, CommMode

DEFAULT = CommConfig()


def _ring_perm(n: int, direction: int = +1):
    return [(i, (i + direction) % n) for i in range(n)]


def _update_at(buf: jax.Array, piece: jax.Array, axis: int, start
               ) -> jax.Array:
    starts = [jnp.int32(0)] * buf.ndim
    starts[axis] = jnp.asarray(start, jnp.int32)
    return lax.dynamic_update_slice(buf, piece.astype(buf.dtype),
                                    tuple(starts))


def _slice_at(src: jax.Array, axis: int, start, size: int) -> jax.Array:
    starts = [jnp.int32(0)] * src.ndim
    starts[axis] = jnp.asarray(start, jnp.int32)
    sizes = list(src.shape)
    sizes[axis] = size
    return lax.dynamic_slice(src, tuple(starts), tuple(sizes))


# ---------------------------------------------------------------------------
# all-gather (zero-copy ring)
# ---------------------------------------------------------------------------

def all_gather(x: jax.Array, axis_name: str,
               config: CommConfig = DEFAULT, *, axis: int = 0) -> jax.Array:
    """All-gather ``x`` (sharded on ``axis``) across ``axis_name``."""
    if config.mode == CommMode.BSP:
        return lax.all_gather(x, axis_name, axis=axis, tiled=True)
    return _ring_all_gather(
        x, axis_name, axis=axis,
        bidirectional=config.mode == CommMode.LCI_DEDICATED)


def _ring_all_gather(x: jax.Array, axis_name: str, *, axis: int,
                     bidirectional: bool) -> jax.Array:
    p = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    shard = x.shape[axis]
    out_shape = x.shape[:axis] + (shard * p,) + x.shape[axis + 1:]
    out = jnp.zeros(out_shape, x.dtype)
    if p == 1:
        return _update_at(out, x, axis, 0)

    sf = (p - 1 + 1) // 2          # forward hops = ceil((P-1)/2)
    sb = (p - 1) - sf              # backward hops

    # Rings are unrolled (p is static inside shard_map): every iteration is
    # visible to XLA's async scheduler (collective-permute-start/done pairs
    # overlap with the dus/compute of the previous arrival), and the whole
    # construct is reverse-mode differentiable (fori_loop is not).
    if not bidirectional or sb == 0:
        cur = x
        for i in range(p):
            out = _update_at(out, cur, axis, ((idx - i) % p) * shard)
            if i < p - 1:
                cur = lax.ppermute(cur, axis_name, _ring_perm(p, +1))
        return out

    # bidirectional (distance-split): exactly sf forward + sb backward hops.
    out = _update_at(out, x, axis, idx * shard)              # self
    cf, cb = x, x
    for j in range(1, sf + 1):
        cf = lax.ppermute(cf, axis_name, _ring_perm(p, +1))
        out = _update_at(out, cf, axis, ((idx - j) % p) * shard)
        if j <= sb:
            cb = lax.ppermute(cb, axis_name, _ring_perm(p, -1))
            out = _update_at(out, cb, axis, ((idx + j) % p) * shard)
    return out


# ---------------------------------------------------------------------------
# all-gather matmul:  Y = allgather(X) @ W   (column-parallel TP with SP)
# ---------------------------------------------------------------------------

def all_gather_matmul(x: jax.Array, w: jax.Array, axis_name: str,
                      config: CommConfig = DEFAULT) -> jax.Array:
    """``x``: (m_shard, ..., k) sharded on dim 0 over ``axis_name``; ``w``:
    (k, n) local (replicated or column-shard).  Returns (m_shard*P, ..., n)
    — ``allgather(x, axis=0) @ w`` with the contraction on the last dim.

    LCI modes compute ``x_i @ w`` while the ring permutes ``x_{i+1}`` —
    the collective-matmul overlap schedule (completion-graph semantics:
    matmul_i depends only on shard_i's arrival, not on the whole gather).
    Rings are unrolled: differentiable, and every transfer is independently
    schedulable against the previous arrival's matmul.
    """
    if config.mode == CommMode.BSP:
        xg = lax.all_gather(x, axis_name, axis=0, tiled=True)
        return jnp.tensordot(xg, w, axes=1).astype(x.dtype)

    p = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    m_shard = x.shape[0]
    out = jnp.zeros((m_shard * p,) + x.shape[1:-1] + (w.shape[1],), x.dtype)

    def mm(cur):
        return jax.lax.dot_general(
            cur, w, (((cur.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if p == 1:
        return _update_at(out, mm(x), 0, 0)

    sf = (p - 1 + 1) // 2
    sb = (p - 1) - sf

    if config.mode == CommMode.LCI_SHARED or sb == 0:
        cur = x
        for i in range(p):
            out = _update_at(out, mm(cur), 0, ((idx - i) % p) * m_shard)
            if i < p - 1:
                cur = lax.ppermute(cur, axis_name, _ring_perm(p, +1))
        return out

    # dedicated: counter-rotating rings, matmul per arrival
    out = _update_at(out, mm(x), 0, idx * m_shard)
    cf, cb = x, x
    for j in range(1, sf + 1):
        cf = lax.ppermute(cf, axis_name, _ring_perm(p, +1))
        out = _update_at(out, mm(cf), 0, ((idx - j) % p) * m_shard)
        if j <= sb:
            cb = lax.ppermute(cb, axis_name, _ring_perm(p, -1))
            out = _update_at(out, mm(cb), 0, ((idx + j) % p) * m_shard)
    return out


# ---------------------------------------------------------------------------
# matmul reduce-scatter:  Y = reduce_scatter(X @ W)  (row-parallel TP)
# ---------------------------------------------------------------------------

def matmul_reduce_scatter(x: jax.Array, w: jax.Array, axis_name: str,
                          config: CommConfig = DEFAULT) -> jax.Array:
    """``x``: (m, k_shard), ``w``: (k_shard, n) sharded on k over
    ``axis_name``.  Returns the row-scattered sum: (m/P, n) on each rank.

    LCI modes ring-accumulate: each step computes one m-slice's partial
    product and adds it to the accumulator arriving from the neighbour —
    the transfer of step i overlaps the matmul of step i+1.  Dedicated mode
    splits the n (feature) axis over two counter-rotating rings.
    """
    p = axis_size(axis_name)
    m = x.shape[0]
    assert m % p == 0, f"matmul_reduce_scatter: m={m} not divisible by P={p}"
    m_shard = m // p

    if config.mode == CommMode.BSP:
        full = jnp.tensordot(x, w, axes=1)
        return lax.psum_scatter(full, axis_name, scatter_dimension=0,
                                tiled=True).astype(x.dtype)

    idx = lax.axis_index(axis_name)

    def one_ring(w_part: jax.Array, direction: int) -> jax.Array:
        def dst(i):
            if direction == +1:
                return (idx + p - 1 - i) % p
            return (idx + i + 1) % p

        def contrib(i):
            piece = _slice_at(x, 0, dst(i) * m_shard, m_shard)
            return jax.lax.dot_general(
                piece, w_part, (((piece.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        acc = contrib(0)
        wire = jnp.bfloat16 if config.wire_bf16 else None
        for i in range(1, p):
            if wire is not None:
                # bf16 on the wire, fp32 local accumulate (CommConfig knob)
                acc = lax.ppermute(acc.astype(wire), axis_name,
                                   _ring_perm(p, direction)
                                   ).astype(jnp.float32)
            else:
                acc = lax.ppermute(acc, axis_name,
                                   _ring_perm(p, direction))
            acc = acc + contrib(i)
        return acc

    n = w.shape[1]
    if config.mode == CommMode.LCI_DEDICATED and p > 1 and n % 2 == 0:
        lo = one_ring(w[:, :n // 2], +1)
        hi = one_ring(w[:, n // 2:], -1)
        return jnp.concatenate([lo, hi], axis=-1).astype(x.dtype)
    return one_ring(w, +1).astype(x.dtype)


# ---------------------------------------------------------------------------
# reduce-scatter / all-reduce on raw tensors (gradient sync path)
# ---------------------------------------------------------------------------

def reduce_scatter(x: jax.Array, axis_name: str,
                   config: CommConfig = DEFAULT, *, axis: int = 0
                   ) -> jax.Array:
    """Ring reduce-scatter of ``x`` along ``axis`` across ``axis_name``."""
    p = axis_size(axis_name)
    if config.mode == CommMode.BSP or x.shape[axis] % p != 0:
        return lax.psum_scatter(x, axis_name, scatter_dimension=axis,
                                tiled=True)
    idx = lax.axis_index(axis_name)
    shard = x.shape[axis] // p

    def one_ring(src: jax.Array, direction: int) -> jax.Array:
        def dst(i):
            if direction == +1:
                return (idx + p - 1 - i) % p
            return (idx + i + 1) % p

        def contrib(i):
            return _slice_at(src, axis, dst(i) * shard, shard
                             ).astype(jnp.float32)

        acc = contrib(0)
        wire = jnp.bfloat16 if config.wire_bf16 else None
        for i in range(1, p):
            if wire is not None:
                acc = lax.ppermute(acc.astype(wire), axis_name,
                                   _ring_perm(p, direction)
                                   ).astype(jnp.float32)
            else:
                acc = lax.ppermute(acc, axis_name,
                                   _ring_perm(p, direction))
            acc = acc + contrib(i)
        return acc.astype(x.dtype)

    feat = x.ndim - 1
    if (config.mode == CommMode.LCI_DEDICATED and p > 1
            and feat != axis and x.shape[feat] % 2 == 0):
        lo, hi = jnp.split(x, 2, axis=feat)
        return jnp.concatenate(
            [one_ring(lo, +1), one_ring(hi, -1)], axis=feat)
    return one_ring(x, +1)


def all_reduce(x: jax.Array, axis_name: str,
               config: CommConfig = DEFAULT) -> jax.Array:
    """All-reduce = ring reduce-scatter + ring all-gather in LCI modes, or a
    single psum in BSP.  Falls back to psum when the leading dim does not
    divide the axis size."""
    if (config.mode == CommMode.BSP or x.ndim == 0
            or x.shape[0] % axis_size(axis_name) != 0):
        return lax.psum(x, axis_name)
    scattered = reduce_scatter(x, axis_name, config, axis=0)
    return all_gather(scattered, axis_name, config, axis=0)


# ---------------------------------------------------------------------------
# all-to-all (MoE dispatch / combine)
# ---------------------------------------------------------------------------

def all_to_all(x: jax.Array, axis_name: str, *, split_axis: int,
               concat_axis: int, config: CommConfig = DEFAULT,
               tiled: bool = True) -> jax.Array:
    """Chunked all-to-all: LCI modes slice a non-participating dim into
    ``n_channels`` chunks issued as independent collectives (XLA overlaps
    them with the surrounding expert compute)."""
    n = config.resolved_channels()
    if config.mode == CommMode.BSP or n <= 1:
        return lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=tiled)
    feat_axis = x.ndim - 1
    if feat_axis in (split_axis, concat_axis) or x.shape[feat_axis] % n != 0:
        return lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=tiled)
    chunks = jnp.split(x, n, axis=feat_axis)
    outs = [lax.all_to_all(c, axis_name, split_axis=split_axis,
                           concat_axis=concat_axis, tiled=tiled)
            for c in chunks]
    return jnp.concatenate(outs, axis=feat_axis)


# ---------------------------------------------------------------------------
# paper §6 collective primitives: dissemination barrier, tree bcast/reduce
# ---------------------------------------------------------------------------

def dissemination_barrier(axis_name: str) -> jax.Array:
    """Dissemination barrier: ceil(log2 P) rounds; returns a token that
    data-depends on every rank (so anything consuming it is ordered after
    the barrier).  Token value == P on every rank (checked in tests)."""
    p = axis_size(axis_name)
    token = jnp.ones((), jnp.int32)
    dist = 1
    while dist < p:
        perm = [(i, (i + dist) % p) for i in range(p)]
        token = token + lax.ppermute(token, axis_name, perm)
        dist *= 2
    return token


def tree_broadcast(x: jax.Array, axis_name: str, *, root: int = 0
                   ) -> jax.Array:
    """Binomial-tree broadcast from ``root`` via masked ppermute rounds."""
    p = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    rel = (idx - root) % p              # root-relative rank
    val = x
    have = rel == 0
    span = 1
    while span < p:
        # relative ranks [0, span) send to [span, 2*span)
        perm = [((i + root) % p, (i + span + root) % p)
                for i in range(span) if i + span < p]
        incoming = lax.ppermute(val, axis_name, perm)
        recv_now = (rel >= span) & (rel < 2 * span)
        val = jnp.where(recv_now & ~have, incoming, val)
        have = have | recv_now
        span *= 2
    return val


def tree_reduce(x: jax.Array, axis_name: str, *, root: int = 0) -> jax.Array:
    """Binomial-tree sum-reduce to ``root`` (other ranks return partials;
    callers wanting all-reduce should tree_broadcast afterwards)."""
    p = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    rel = (idx - root) % p
    val = x
    span = 1
    while span < p:
        # relative ranks with rel % 2span == span send to rel - span
        perm = [((i + root) % p, (i - span + root) % p)
                for i in range(p) if i % (2 * span) == span]
        incoming = lax.ppermute(val, axis_name, perm)
        is_recv = (rel % (2 * span) == 0) & (rel + span < p)
        val = jnp.where(is_recv, val + incoming, val)
        span *= 2
    return val
