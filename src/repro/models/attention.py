"""Attention: chunked flash attention (jnp), GQA, SWA, and flash-decode.

Layout convention everywhere: activations are **seq-major local view**
``(s_local, batch, ...)`` — the natural layout for sequence parallelism
(the SP dim is dim 0, which is what the ring collectives shard).

Two tensor-parallel plans (picked by :func:`repro.models.blocks.tp_plan`):

* **Plan A (sharded heads)** — q/k/v for *all* sequence positions but only
  the local head shard; entered via ``Comm.ag_matmul`` (ring-overlapped).
* **Plan B (replicated heads)** — q for *local* sequence rows only, all
  heads; K/V projected locally and ring-allgathered over the model axis.
  Used when ``n_heads % tp != 0`` (gemma3's 4 heads, hymba's 25, whisper's
  6); zero redundant FLOPs, and the only collective is the small KV gather.

The quadratic part is computed block-by-block with an online softmax — the
flash-attention recurrence expressed as ``lax.scan`` so that (a) the HLO
stays O(1) in sequence length, and (b) peak memory is O(s·d + block²).
The Pallas kernel in :mod:`repro.kernels.flash_attention` implements the
same recurrence with explicit VMEM tiling for TPU; this module is also its
reference oracle (they are tested against each other).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _pick_block(s: int, preferred: int) -> int:
    """Largest divisor of ``s`` that is <= preferred (falls back to s)."""
    b = min(preferred, s)
    while s % b:
        b -= 1
    return max(b, 1)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window=0,
                    q_offset=0, block_q: int = 256, block_k: int = 512,
                    scale: Optional[float] = None) -> jax.Array:
    """Chunked attention with online softmax.

    q: (sq, b, hq, dh); k/v: (skv, b, hkv, dh) with hq % hkv == 0 (GQA).
    ``q_offset`` — global position of q row 0 (SP: rank * s_local).
    ``window`` — sliding-window attention (key j visible to query i iff
    ``i - window < j <= i`` in global positions).  May be a *traced* scalar
    (layer-patterned SWA: the 5:1 local/global choice is data, keeping one
    collective path through the scan body); 0/None disables.  ``causal=
    False`` with no window is full bidirectional (encoder/cross-attention).
    v may be narrower than q and k (latent attention: dv < dh);
    ``scale`` defaults to 1/sqrt(dh).
    Returns (sq, b, hq, dv) in q.dtype; softmax in fp32.
    """
    sq, b, hq, dh = q.shape
    skv, _, hkv, _ = k.shape
    dv = v.shape[-1]
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    scale = 1.0 / math.sqrt(dh) if scale is None else scale

    bq = _pick_block(sq, block_q)
    bk = _pick_block(skv, block_k)
    nq, nk = sq // bq, skv // bk

    # (nq, bq, b, hkv, g, dh) — blocked, GQA-grouped
    qb = q.reshape(nq, bq, b, hkv, g, dh).astype(jnp.float32) * scale
    kb = k.reshape(nk, bk, b, hkv, dh).astype(jnp.float32)
    vb = v.reshape(nk, bk, b, hkv, dv).astype(jnp.float32)
    q_offset = jnp.asarray(q_offset, jnp.int32)
    use_window = window is not None and not (
        isinstance(window, int) and window == 0)
    window = jnp.asarray(window if use_window else 0, jnp.int32)

    def q_block(qi, q_blk):
        q_pos = q_offset + qi * bq + jnp.arange(bq, dtype=jnp.int32)

        def kv_step(carry, inputs):
            m, l, acc = carry
            ki, k_blk, v_blk = inputs
            k_pos = ki * bk + jnp.arange(bk, dtype=jnp.int32)
            # scores: (b, hkv, g, bq, bk)
            s = jnp.einsum("qbhgd,kbhd->bhgqk", q_blk, k_blk)
            mask = jnp.ones((bq, bk), bool)
            if causal:
                mask &= q_pos[:, None] >= k_pos[None, :]
            if use_window:
                mask &= k_pos[None, :] > q_pos[:, None] - window
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bhgqk,kbhd->bhgqd", p, v_blk)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, g, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, bq), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, bq, dv), jnp.float32)
        (m, l, acc), _ = lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.arange(nk, dtype=jnp.int32), kb, vb))
        out = acc / jnp.maximum(l, 1e-37)[..., None]
        # (b, hkv, g, bq, dv) -> (bq, b, hkv, g, dv)
        return jnp.transpose(out, (3, 0, 1, 2, 4))

    outs = lax.map(lambda args: q_block(*args),
                   (jnp.arange(nq, dtype=jnp.int32), qb))
    out = outs.reshape(sq, b, hkv, g, dv).reshape(sq, b, hq, dv)
    return out.astype(q.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     *, valid_len=None, kv_offset=0, window=0,
                     q_pos=None, block_k: int = 1024) -> tuple:
    """One-token attention against a (possibly sharded) KV slice.

    q: (b, hq, dh); k_cache/v_cache: (skv_local, hkv, b, dh), the serving
    cache's own order (:mod:`repro.serving.engine`).
    Returns ``(num, m, l)`` — the *partial* flash-decode triple:
    num (b, hq, dh) unnormalized output, m (b, hq) running max, l (b, hq)
    exp-sum.  Shard-parallel callers combine partials across the KV-sharding
    axis with :func:`combine_decode_partials`; single-shard callers finish
    with ``num / l``.

    ``kv_offset`` — global position of cache row 0 (seq-sharded cache);
    ``valid_len`` — #globally valid cache rows (traced ok); ``q_pos`` — the
    query's global position (defaults to valid_len - 1 + nothing... callers
    pass it explicitly for windowed attention).
    """
    skv, hkv, b, dh = k_cache.shape
    hq = q.shape[1]
    g = hq // hkv
    scale = 1.0 / math.sqrt(dh)

    qf = q.reshape(b, hkv, g, dh).astype(jnp.float32) * scale
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    pos = kv_offset + jnp.arange(skv, dtype=jnp.int32)
    valid = jnp.ones((skv,), bool)
    if valid_len is not None:
        valid &= pos < valid_len
    use_window = window is not None and not (
        isinstance(window, int) and window == 0)
    if use_window and q_pos is not None:
        valid &= pos > q_pos - jnp.asarray(window, jnp.int32)

    bk = _pick_block(skv, block_k)
    nk = skv // bk
    kb = kf.reshape(nk, bk, hkv, b, dh)
    vb = vf.reshape(nk, bk, hkv, b, dh)
    maskb = valid.reshape(nk, bk)

    def step(carry, inputs):
        m, l, acc = carry
        k_blk, v_blk, msk = inputs
        s = jnp.einsum("bhgd,khbd->bhgk", qf, k_blk)
        s = jnp.where(msk[None, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgk,khbd->bhgd", p, v_blk)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, dh), jnp.float32)
    (m, l, acc), _ = lax.scan(step, (m0, l0, a0), (kb, vb, maskb))
    return (acc.reshape(b, hq, dh), m.reshape(b, hq), l.reshape(b, hq))


def combine_decode_partials(num, m, l, comm) -> jax.Array:
    """Combine flash-decode partials across the model axis (psum/pmax).

    The LCI reading: each KV shard is an independent *channel* whose partial
    completes asynchronously; the combine is the synchronizer (multi-signal
    completion object) joining them.
    """
    m_glob = comm.pmax_model(m)
    corr = jnp.exp(m - m_glob)
    l_glob = comm.psum_model(l * corr)
    num_glob = comm.psum_model(num * corr[..., None])
    return (num_glob / jnp.maximum(l_glob, 1e-37)[..., None])


def attention_reference(q, k, v, *, causal=True, window=0, q_offset=0):
    """O(s²)-memory oracle used by tests (materializes the score matrix)."""
    sq, b, hq, dh = q.shape
    skv, _, hkv, _ = k.shape
    g = hq // hkv
    scale = 1.0 / math.sqrt(dh)
    qf = q.reshape(sq, b, hkv, g, dh).astype(jnp.float32)
    s = jnp.einsum("qbhgd,kbhd->bhgqk", qf, k.astype(jnp.float32)) * scale
    q_pos = q_offset + jnp.arange(sq)[:, None]
    k_pos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,kbhd->qbhgd", p, v.astype(jnp.float32))
    return out.reshape(sq, b, hq, dh).astype(q.dtype)
