"""Shared layers: norms, RoPE, MLPs, vocab-parallel embedding & loss.

All functions take the seq-major local view ``(s_local, b, d)`` and a
:class:`repro.distributed.Comm`.  Norm math is fp32 regardless of payload
dtype.  The embedding table is vocab-sharded over the model axis (TP) and
feature-sharded over data (FSDP); logits are never materialized at full
vocab width — the cross-entropy is computed vocab-parallel (max/sum-exp
psums over the model axis), which is what makes 256k-vocab configs fit.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, w: Optional[jax.Array], eps: float = 1e-6
             ) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    if w is not None:
        y = y * w.astype(jnp.float32)
    return y.astype(x.dtype)


def layer_norm(x: jax.Array, w: Optional[jax.Array],
               b: Optional[jax.Array] = None, eps: float = 1e-5
               ) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    if w is not None:
        y = y * w.astype(jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    return y.astype(x.dtype)


def apply_norm(kind: str, x: jax.Array, w: Optional[jax.Array],
               eps: Optional[float] = None) -> jax.Array:
    """``eps`` None keeps each kind's default."""
    kw = {} if eps is None else {"eps": eps}
    if kind == "rmsnorm":
        return rms_norm(x, w, **kw)
    if kind == "layernorm":
        return layer_norm(x, w, **kw)
    if kind == "layernorm_np":          # OLMo: non-parametric LN
        return layer_norm(x, None, **kw)
    raise ValueError(f"unknown norm {kind!r}")


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(dh: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float
               ) -> jax.Array:
    """x: (s, b, h, dh); positions: (s,) global positions (SP-offset)."""
    dh = x.shape[-1]
    freqs = rope_frequencies(dh, theta)                       # (dh/2,)
    angles = positions.astype(jnp.float32)[:, None] * freqs   # (s, dh/2)
    cos = jnp.cos(angles)[:, None, None, :]
    sin = jnp.sin(angles)[:, None, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_rope_pairs(x: jax.Array, positions: jax.Array, theta: float
                     ) -> jax.Array:
    """Rotary over adjacent pairs ``(x[2i], x[2i+1])`` at frequency ``i``,
    as DeepSeek-V2/V3's modelling code pairs them (it de-interleaves and
    rotates halves; the result is returned here in that de-interleaved
    order, which both q and k share, so their dot product is the same).
    x: (s, b, h, dh); positions: (s,)."""
    dh = x.shape[-1]
    freqs = rope_frequencies(dh, theta)
    angles = positions.astype(jnp.float32)[:, None] * freqs
    cos = jnp.cos(angles)[:, None, None, :]
    sin = jnp.sin(angles)[:, None, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(s: int, d: int, offset=0) -> jax.Array:
    """Whisper-style sinusoidal embeddings: (s, d)."""
    pos = jnp.arange(s, dtype=jnp.float32) + offset
    half = d // 2
    freqs = jnp.exp(-math.log(10_000.0) * jnp.arange(half) / max(half - 1, 1))
    ang = pos[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# MLPs (TP: w_in column-parallel, w_out row-parallel)
# ---------------------------------------------------------------------------

def mlp_activation(kind: str, h: jax.Array) -> jax.Array:
    """Apply the nonlinearity; swiglu expects fused gate|up on last dim."""
    if kind == "swiglu":
        gate, up = jnp.split(h, 2, axis=-1)
        return jax.nn.silu(gate.astype(jnp.float32)).astype(h.dtype) * up
    if kind == "geglu":                  # gemma: gated tanh-GELU
        gate, up = jnp.split(h, 2, axis=-1)
        return jax.nn.gelu(gate.astype(jnp.float32),
                           approximate=True).astype(h.dtype) * up
    if kind == "gelu":
        return jax.nn.gelu(h.astype(jnp.float32)).astype(h.dtype)
    if kind == "relu2":                  # Nemotron/Minitron squared ReLU
        r = jax.nn.relu(h)
        return r * r
    raise ValueError(f"unknown mlp {kind!r}")


def mlp_block(x: jax.Array, w_in: jax.Array, w_out: jax.Array, kind: str,
              comm) -> jax.Array:
    """x: (s_local, b, d) -> (s_local, b, d).  ag_matmul in, matmul_rs out
    (the Megatron-SP schedule on LCI ring collectives)."""
    h = comm.ag_matmul(x, w_in)          # (s, b, ff_local[*2 if swiglu])
    h = mlp_activation(kind, h)
    return comm.matmul_rs(h, w_out)      # (s_local, b, d)


# ---------------------------------------------------------------------------
# vocab-parallel embedding + loss
# ---------------------------------------------------------------------------

def embed_tokens(tokens: jax.Array, emb: jax.Array, comm, *,
                 scale_by_sqrt_dim: bool = False) -> jax.Array:
    """tokens: (s_local, b) int32; emb: (V_local, d) vocab shard.
    Returns the *seq-local* embeddings (s_local, b, d).

    Tokens are seq-sharded over the same model axis that shards the vocab,
    so the assembly is: all-gather the (tiny, int32) token ids, look up the
    locally-owned vocab rows for the FULL sequence, then **reduce-scatter
    over the sequence axis** — one collective whose bytes equal a single
    activation scatter, and whose LCI-mode lowering is the ring schedule.
    (A psum here would be wrong: each rank's partial covers different
    vocab rows but the *same* full sequence; rs sums partials and hands
    each rank back its own rows.)
    """
    v_local, d = emb.shape
    tokens_full = comm.ag_seq(tokens)                  # (s, b)
    rank = comm.model_index()
    local = tokens_full - rank * v_local
    valid = (local >= 0) & (local < v_local)
    rows = jnp.take(emb, jnp.clip(local, 0, v_local - 1), axis=0)
    rows = jnp.where(valid[..., None], rows, 0).astype(jnp.float32)
    out = comm.rs_seq(rows, axis=0)                    # (s_local, b, d)
    if scale_by_sqrt_dim:
        out = out * math.sqrt(d)
    return out.astype(emb.dtype)


def lm_head_loss(x: jax.Array, emb: jax.Array, labels: jax.Array, comm, *,
                 real_vocab: int, z_coef: float = 0.0,
                 ignore_label: int = -100):
    """Vocab-parallel cross-entropy.

    x: (s, b, d) FULL-sequence activations (callers ag_seq first);
    emb: (V_local, d) head shard (tied or untied); labels: (s, b) global ids.
    Returns (sum_loss, n_tokens) — callers combine across data shards.
    Full-vocab logits never exist: only (s, b, V_local) per rank.
    """
    v_local = emb.shape[0]
    rank = comm.model_index()
    logits = jnp.tensordot(x.astype(jnp.float32),
                           emb.astype(jnp.float32).T, axes=1)
    # mask padded vocab slots (rows beyond the real vocab)
    gid = rank * v_local + jnp.arange(v_local)
    logits = jnp.where(gid[None, None, :] < real_vocab, logits, -1e30)

    # the max is for numerical stability only — constant wrt gradients.
    # stop_gradient BEFORE pmax: pmax has no JVP rule, so it must only ever
    # see non-differentiated values.
    m = comm.pmax_model(jax.lax.stop_gradient(logits.max(axis=-1)))
    # grad-exact psums: the CE is replicated across the model axis, so the
    # correct transpose of these reductions is identity (see Comm.psum_model_ge)
    se = comm.psum_model_ge(jnp.exp(logits - m[..., None]).sum(axis=-1))
    lse = m + jnp.log(se)                                     # (s, b)

    local_label = labels - rank * v_local
    valid = (local_label >= 0) & (local_label < v_local)
    tl_local = jnp.take_along_axis(
        logits, jnp.clip(local_label, 0, v_local - 1)[..., None], axis=-1
    )[..., 0]
    target_logit = comm.psum_model_ge(jnp.where(valid, tl_local, 0.0))

    keep = labels != ignore_label
    per_tok = (lse - target_logit) * keep
    if z_coef:
        per_tok = per_tok + z_coef * (lse * keep) ** 2
    return per_tok.sum(), keep.sum()


def lm_head_logits(x: jax.Array, emb: jax.Array, comm, *,
                   real_vocab: int) -> jax.Array:
    """Decode-path logits: x (b, d) one position -> (b, V_local) local
    shard (the serving engine samples vocab-parallel: argmax via local
    top-1 + psum-argmax combine)."""
    v_local = emb.shape[0]
    rank = comm.model_index()
    logits = jnp.tensordot(x.astype(jnp.float32),
                           emb.astype(jnp.float32).T, axes=1)
    gid = rank * v_local + jnp.arange(v_local)
    return jnp.where(gid[None, :] < real_vocab, logits, -1e30)


def greedy_sample(logits_local: jax.Array, comm) -> jax.Array:
    """Vocab-parallel argmax: (b, V_local) -> (b,) global token ids."""
    v_local = logits_local.shape[-1]
    rank = comm.model_index()
    local_best = jnp.argmax(logits_local, axis=-1)            # (b,)
    local_val = jnp.take_along_axis(
        logits_local, local_best[:, None], axis=-1)[:, 0]
    best_val = comm.pmax_model(local_val)
    mine = local_val >= best_val                              # ties: lowest rank
    gid = rank * v_local + local_best
    cand = jnp.where(mine, gid, jnp.iinfo(jnp.int32).max)
    return -comm.pmax_model(-cand)                            # global min
