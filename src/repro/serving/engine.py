"""Serving engine: prefill and single-token decode for every arch family.

Cache layout (global view; local view divides by the mesh):

    k/v      (L, S, n_kv, B, dh)     seq-sharded over ``model`` (and over
                                     ``data`` too when B == 1: long-context
                                     flash-decode over the joint axis)
    ssm_state (L, B, H, N, P)        heads over ``model``, batch over ``data``
    conv_tail (L, K-1, B, d_inner)   channels with the heads
    cross_k/v (L, T, B, n_kv, dh)    (enc-dec / VLM) precomputed memory KV
    latent   (L, B, S, W)            (latent attention) the normed kv
                                     latent and the rotary key (r + r_pe,
                                     zero-padded to W, a multiple of 128
                                     lanes), in place of K/V; seq-sharded
                                     like them; rows major, as the
                                     batched attention products read it
    expert_load (3, L_moe, E_held)   (MoE) per held expert, summed over
                                     steps: tokens routed, most routed in
                                     one step, tokens combined
    latent_blocks ()                 (latent attention) row blocks of the
                                     latent cache its attention read,
                                     summed over layers, shards and steps

K/V are stored in the order the layer scan's loop keeps its carry in, so
XLA transposes nothing at the loop's edges; each step writes one
``(n_kv, B, dh)`` row per layer in place and attention reads the layer
where it lies.  The latent cache is not carried: the loop only reads it,
each layer attends to its new row beside the cached ones, and the step
writes the rows of all layers in place after the loop (carried and
written per layer, XLA lays it out for the row write and copies each
layer's slice into the order the attention products read).  Latent
attention reads a layer's cached rows inside one kernel
(``kernels/latent_decode``): each block of rows that holds a live row
once, for both the scores and the values, and the blocks past the live
front not at all.

Decode dataflow per layer (the LCI reading: every KV shard is a *channel*;
partial attention results are joined by a synchronizer — implemented as
the flash-decode max/sum-exp psum combine):

    x (b, d) replicated over model
      -> q/k/v local head shards   (tiny matmuls)
      -> all-gather q,kv over model (bytes ~ b·h·dh: inject-protocol small)
      -> one-row cache write at (layer, ``pos``) on the owning seq shard
      -> decode_attention against the LOCAL seq shard (all heads)
      -> combine partials (psum/pmax over the KV-sharding axes)
      -> out-projection row shard + psum

Weights keep their at-rest layout: TP over ``model``; the FSDP dim over
``data`` is gathered per layer exactly like training ("FSDP-serving") —
HBM-bound deployments trade ICI for memory.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from jax.lax import axis_size

from repro.distributed.comm import Comm, _axes, local_comm
from repro.models.attention import (NEG_INF, combine_decode_partials,
                                    decode_attention)
from repro.models.blocks import TPPlan, layer_window, tp_plan
from repro.models.common import ModelConfig, shard_decisions
from repro.models.layers import (apply_norm, apply_rope, apply_rope_pairs,
                                 greedy_sample, lm_head_logits,
                                 mlp_activation, rms_norm)
from repro.models.moe import moe_decode
from repro.models.ssm import ssd_decode_step
from repro.serving.kv_cache import latent_width
from repro.models import lm as lm_mod


# ---------------------------------------------------------------------------
# cache container
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DecodeCache:
    # (L, S_loc, n_kv, b, dh): the layer loop's own layout, so the donated
    # cache enters and leaves the loop without a transpose
    k: Optional[jax.Array] = None
    v: Optional[jax.Array] = None
    ssm_state: Optional[jax.Array] = None    # (L, b, H_loc, N, P)
    conv_tail: Optional[jax.Array] = None    # (L, K-1, b, di_loc)
    cross_k: Optional[jax.Array] = None      # (L, T, b, n_kv, dh)
    cross_v: Optional[jax.Array] = None
    length: Optional[jax.Array] = None       # () int32 — #valid positions
    latent: Optional[jax.Array] = None       # (L, b, S_loc, W)
    expert_load: Optional[jax.Array] = None  # (3, L_moe, E_held) int32
    latent_blocks: Optional[jax.Array] = None  # () int32


jax.tree_util.register_pytree_node(
    DecodeCache,
    lambda c: ((c.k, c.v, c.ssm_state, c.conv_tail, c.cross_k, c.cross_v,
                c.length, c.latent, c.expert_load, c.latent_blocks), None),
    lambda _, xs: DecodeCache(*xs))

#: ``jax.named_scope`` of the layer scan's per-layer cache reads and
#: writes: device-trace readers find the cache traffic by this name
CACHE_IO = "cache_io"
#: ``jax.named_scope`` of latent attention's decode: its projections, the
#: query absorption, the attention over the latent rows and the output
#: absorption (the latent row's write is under ``CACHE_IO``)
MLA_ATTN = "mla_attn"


def _has_attn(cfg: ModelConfig) -> bool:
    """Per-head K/V in the cache."""
    return cfg.family != "ssm" and not cfg.is_mla


def _has_ssm(cfg: ModelConfig) -> bool:
    return cfg.family in ("ssm", "hybrid")


def _n_cross(cfg: ModelConfig) -> int:
    if cfg.family == "vlm":
        return cfg.n_layers // cfg.cross_attn_every
    if cfg.is_encdec:
        return cfg.n_layers
    return 0


def init_cache(cfg: ModelConfig, seq_len: int, batch: int, *,
               kv_shards: int = 1, data_shards: int = 1,
               n_memory: int = 0) -> DecodeCache:
    """GLOBAL-shape cache (callers shard via :func:`cache_pspecs`)."""
    L = cfg.n_layers - _n_cross(cfg) if cfg.family == "vlm" else cfg.n_layers
    c = DecodeCache(length=jnp.zeros((), jnp.int32))
    if _has_attn(cfg):
        dh = cfg.resolved_head_dim
        shape = (L, seq_len, cfg.n_kv_heads, batch, dh)
        c.k = jnp.zeros(shape, cfg.dtype)
        c.v = jnp.zeros(shape, cfg.dtype)
    if _has_ssm(cfg):
        c.ssm_state = jnp.zeros(
            (cfg.n_layers, batch, cfg.ssm_heads, cfg.ssm_state,
             cfg.ssm_headdim), jnp.float32)
        c.conv_tail = jnp.zeros(
            (cfg.n_layers, cfg.ssm_conv_kernel - 1, batch, cfg.ssm_d_inner),
            cfg.dtype)
    if cfg.is_mla:
        c.latent = jnp.zeros((L, batch, seq_len, latent_width(cfg)),
                             cfg.dtype)
        c.latent_blocks = jnp.zeros((), jnp.int32)
    if cfg.family == "moe":
        c.expert_load = jnp.zeros(
            (3, cfg.n_stacked_layers, cfg.n_experts_held), jnp.int32)
    nx = _n_cross(cfg)
    if nx and n_memory:
        xshape = (nx, n_memory, batch, cfg.n_kv_heads,
                  cfg.resolved_head_dim)
        c.cross_k = jnp.zeros(xshape, cfg.dtype)
        c.cross_v = jnp.zeros(xshape, cfg.dtype)
    return c


def cache_pspecs(cfg: ModelConfig, *, batch: int, model_axis="model",
                 data_axis="data"):
    """PartitionSpecs for the cache: seq over model (+data when B==1),
    batch over data."""
    from jax.sharding import PartitionSpec as P
    daxes = _axes(data_axis)
    joint = batch == 1
    seq_axes = ((model_axis,) + daxes) if joint else (model_axis,)
    batch_spec = None if joint else daxes
    dec = shard_decisions(cfg)
    ssm_head = model_axis if dec["ssm"] else None
    return DecodeCache(
        k=P(None, seq_axes, None, batch_spec, None) if _has_attn(cfg) else None,
        v=P(None, seq_axes, None, batch_spec, None) if _has_attn(cfg) else None,
        ssm_state=(P(None, batch_spec, ssm_head, None, None)
                   if _has_ssm(cfg) else None),
        conv_tail=(P(None, None, batch_spec, ssm_head)
                   if _has_ssm(cfg) else None),
        cross_k=(P(None, None, batch_spec, None, None) if _n_cross(cfg)
                 else None),
        cross_v=(P(None, None, batch_spec, None, None) if _n_cross(cfg)
                 else None),
        length=P(),
        latent=P(None, batch_spec, seq_axes, None) if cfg.is_mla else None,
        expert_load=P() if cfg.family == "moe" else None,
        latent_blocks=P() if cfg.is_mla else None,
    )


# ---------------------------------------------------------------------------
# decode helpers
# ---------------------------------------------------------------------------

def _embed_flat(tokens: jax.Array, emb: jax.Array, comm: Comm, *,
                scale: bool) -> jax.Array:
    """tokens (b,) replicated over model -> (b, d) via vocab-shard psum."""
    v_local, d_loc = emb.shape
    rank = comm.model_index()
    local = tokens - rank * v_local
    valid = (local >= 0) & (local < v_local)
    rows = jnp.take(emb, jnp.clip(local, 0, v_local - 1), axis=0)
    rows = jnp.where(valid[:, None], rows, 0).astype(jnp.float32)
    out = comm.psum_model(rows)
    if scale:
        out = out * jnp.sqrt(jnp.float32(out.shape[-1]))
    return out.astype(emb.dtype)


def _in_proj(x, w, comm: Comm) -> jax.Array:
    """``x @ w`` for a column-parallel weight, its FSDP dim (0) gathered."""
    return jnp.tensordot(x, comm.weight(w, fsdp_axis=0), axes=1)


def _row_parallel_out(x_loc, w, *, comm: Comm,
                      shard_model: bool) -> jax.Array:
    """Row-parallel exit (wo / w_out): gather the FSDP dim (1), local
    product, psum over model when the rows are model-sharded."""
    y = jnp.tensordot(x_loc, comm.weight(w, fsdp_axis=1), axes=1)
    return comm.psum_model(y) if shard_model else y


def _kv_axes(comm: Comm, *, joint: bool):
    """Axes the KV seq dim is sharded over (model [+ data for B==1]), in
    :func:`cache_pspecs`' order, so shard ``i`` holds global rows
    ``i * S_loc`` on."""
    axes = list(_axes(comm.model_axis))
    if joint:
        axes = axes + list(_axes(comm.data_axis))
    return tuple(axes)


def _axes_index(comm: Comm, axes) -> jax.Array:
    idx = jnp.zeros((), jnp.int32)
    for a in axes:
        idx = idx * axis_size(a) + jax.lax.axis_index(a)
    return idx


def _psum_axes(x, axes):
    for a in axes:
        x = jax.lax.psum(x, a)
    return x


def _pmax_axes(x, axes):
    for a in axes:
        x = jax.lax.pmax(x, a)
    return x


def _write_row(buf, row, idx, at, owns):
    """Write one token's ``(n_kv, b, dh)`` row into the whole cache
    buffer ``(L, S_loc, n_kv, b, dh)`` at (layer ``idx``, row ``at``), in
    place; a shard that does not own the position writes back the row it
    read."""
    start = (idx, at, 0, 0, 0)
    old = jax.lax.dynamic_slice(buf, start, (1, 1) + buf.shape[2:])
    new = jnp.where(owns, row.astype(buf.dtype)[None, None], old)
    return jax.lax.dynamic_update_slice(buf, new, start)


def _decode_attn_layer(x, lp, cfg, comm: Comm, plan: TPPlan, k_all, v_all,
                       idx, pos, window, *, joint_kv: bool,
                       prefix: str = "", memory_kv=None):
    """One attention layer for a single token.

    x (b, d) replicated over model; k/v_all (L, S_loc, nkv, b, dh) the
    whole local seq shard, of which this layer is ``idx`` (all three None
    for cross-attention over ``memory_kv``).  Returns (out (b, d), k_all',
    v_all') with the token's row written.
    """
    dh = cfg.resolved_head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads

    # local projections, then tiny gathers to full heads
    q = _in_proj(x, lp[prefix + "wq"], comm)
    if plan.shard_heads:
        q = comm.ag_seq(q.T, axis=0).T             # (b, nq*dh)
    q = q.reshape(-1, nq, dh)

    is_cross = memory_kv is not None
    if is_cross:
        k_new = v_new = None
    else:
        k_new = _in_proj(x, lp[prefix + "wk"], comm)
        v_new = _in_proj(x, lp[prefix + "wv"], comm)
        if plan.shard_kv:
            k_new = comm.ag_seq(k_new.T, axis=0).T
            v_new = comm.ag_seq(v_new.T, axis=0).T
        k_new = k_new.reshape(-1, nkv, dh)
        v_new = v_new.reshape(-1, nkv, dh)

    if cfg.qk_norm:
        q = rms_norm(q, lp[prefix + "q_norm"])
        if not is_cross:
            k_new = rms_norm(k_new, lp[prefix + "k_norm"])
    if not is_cross:
        posv = jnp.full((1,), pos, jnp.int32)
        q = apply_rope(q[None], posv, cfg.rope_theta)[0]
        k_new = apply_rope(k_new[None], posv, cfg.rope_theta)[0]

        # cache write on the owning seq shard
        axes = _kv_axes(comm, joint=joint_kv)
        shard_len = k_all.shape[1]
        my_idx = _axes_index(comm, axes)
        my_start = my_idx * shard_len
        rel = pos - my_start
        owns = (rel >= 0) & (rel < shard_len)
        rel_c = jnp.clip(rel, 0, shard_len - 1)
        with jax.named_scope(CACHE_IO):
            k_all = _write_row(k_all, k_new.swapaxes(0, 1), idx, rel_c, owns)
            v_all = _write_row(v_all, v_new.swapaxes(0, 1), idx, rel_c, owns)
            k_layer, v_layer = k_all[idx], v_all[idx]
        num, m, l = decode_attention(
            q, k_layer, v_layer, valid_len=pos + 1, kv_offset=my_start,
            window=window, q_pos=pos)
        m_g = _pmax_axes(m, axes)
        corr = jnp.exp(m - m_g)
        l_g = _psum_axes(l * corr, axes)
        num_g = _psum_axes(num * corr[..., None], axes)
        attn = (num_g / jnp.maximum(l_g, 1e-37)[..., None])
    else:
        mk, mv = memory_kv                        # (T, b, nkv, dh) local full
        num, m, l = decode_attention(q, mk.swapaxes(1, 2),
                                     mv.swapaxes(1, 2), valid_len=None)
        attn = num / jnp.maximum(l, 1e-37)[..., None]

    attn = attn.reshape(-1, nq * dh).astype(x.dtype)
    if plan.shard_heads:
        nq_l = plan.q_local(cfg)
        start = comm.model_index() * (nq_l * dh)
        attn_loc = jax.lax.dynamic_slice_in_dim(attn, start, nq_l * dh,
                                                axis=1)
        out = _row_parallel_out(attn_loc, lp[prefix + "wo"], comm=comm,
                                shard_model=True)
    else:
        out = _row_parallel_out(attn, lp[prefix + "wo"], comm=comm,
                                shard_model=False)
    return out, k_all, v_all


def _decode_mla(x, lp, cfg: ModelConfig, comm: Comm, lat_all, idx, pos, *,
                joint_kv: bool):
    """Latent attention for one token per row, in the absorbed form.

    x (b, d) pre-normed, replicated over model; ``lat_all`` (L, b, S_loc,
    W) the local seq shard of the latent cache, of which this layer is
    ``idx``, rows below ``pos`` written.  The query's nope part is taken
    through ``wk_b`` into the latent space, so a score is one dot with a
    cached row [latent, rotary key]; the attention's output stays in the
    latent space until ``wv_b`` takes it to the heads.  K and V are never
    expanded.  The new token's own row is attended beside the cached
    ones.  Returns (out (b, d), row (b, W) for position ``pos``, in the
    cache's dtype)."""
    nq, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    f32 = jnp.float32
    _, b, shard_len, width = lat_all.shape
    posv = jnp.full((1,), pos, jnp.int32)
    with jax.named_scope(MLA_ATTN):
        q = jnp.tensordot(x, comm.weight(lp["wq"], fsdp_axis=0),
                          axes=1).reshape(b, nq, dn + dr)
        kv_a = jnp.tensordot(x, comm.weight(lp["wkv_a"], fsdp_axis=0),
                             axes=1)                       # (b, r + r_pe)
        c = rms_norm(kv_a[:, :r], lp["kv_norm"], eps=lm_mod.MLA_KV_NORM_EPS)
        k_pe = apply_rope_pairs(kv_a[None, :, None, r:], posv,
                                cfg.rope_theta)[0, :, 0]
        q_pe = apply_rope_pairs(q[None, ..., dn:], posv, cfg.rope_theta)[0]
        w_uk = comm.weight(lp["wk_b"], fsdp_axis=0).reshape(r, nq, dn)
        q_lat = jnp.einsum("bhn,rhn->bhr", q[..., :dn], w_uk,
                           preferred_element_type=f32)
        pad = width - r - dr
        qc = (jnp.concatenate([q_lat, q_pe.astype(f32),
                               jnp.zeros((b, nq, pad), f32)], axis=-1)
              * (dn + dr) ** -0.5)                        # (b, nq, W)
        row = jnp.concatenate([c, k_pe, jnp.zeros((b, pad), c.dtype)],
                              axis=-1).astype(lat_all.dtype)
        # the cached rows below pos, each live block of the layer read once
        # for both products where it lies, the dead ones not at all; then
        # the new row (on the shard that owns its position).  Imported
        # here: Pallas takes over a second to import, and only latent
        # attention runs a kernel
        from repro.kernels.latent_decode.ops import latent_decode
        axes, rel = _latent_shard(pos, shard_len, comm, joint_kv=joint_kv)
        owns = (rel >= 0) & (rel < shard_len)
        num, m, l = latent_decode(qc, lat_all, idx,
                                  jnp.clip(rel, 0, shard_len), rank=r)
        s_new = jnp.einsum("bhc,bc->bh", qc, row.astype(f32))
        s_new = jnp.where(owns, s_new, NEG_INF)
        m_new = jnp.maximum(m, s_new)
        corr = jnp.exp(m - m_new)
        p_new = jnp.exp(s_new - m_new)
        num = (num * corr[..., None]
               + p_new[..., None] * row[:, None, :r].astype(f32))
        l = l * corr + p_new
        m_g = _pmax_axes(m_new, axes)
        corr = jnp.exp(m_new - m_g)
        l_g = _psum_axes(l * corr, axes)
        num_g = _psum_axes(num * corr[..., None], axes)
        o_lat = num_g / jnp.maximum(l_g, 1e-37)[..., None]  # (b, nq, r)
        w_uv = comm.weight(lp["wv_b"], fsdp_axis=0).reshape(r, nq, dv)
        o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(x.dtype), w_uv,
                       preferred_element_type=f32)
        out = jnp.tensordot(o.reshape(b, nq * dv).astype(x.dtype),
                            comm.weight(lp["wo"], fsdp_axis=1), axes=1)
    return out, row


def _latent_shard(pos, shard_len: int, comm: Comm, *, joint_kv: bool):
    """(the axes the latent cache's rows are sharded over, ``pos`` less
    this shard's first row)."""
    axes = _kv_axes(comm, joint=joint_kv)
    return axes, pos - _axes_index(comm, axes) * shard_len


def _write_latent_rows(lat_all, rows, pos, comm: Comm, *, joint_kv: bool):
    """Every layer's row ``rows`` (L, b, W) into the latent cache (L, b,
    S_loc, W) at position ``pos``, in place, on the shard that owns it."""
    shard_len = lat_all.shape[2]
    _, rel = _latent_shard(pos, shard_len, comm, joint_kv=joint_kv)
    owns = (rel >= 0) & (rel < shard_len)
    start = (0, 0, jnp.clip(rel, 0, shard_len - 1), 0)
    with jax.named_scope(CACHE_IO):
        old = jax.lax.dynamic_slice(lat_all, start, rows.shape[:2] + (1,)
                                    + rows.shape[2:])
        new = jnp.where(owns, rows[:, :, None], old)
        return jax.lax.dynamic_update_slice(lat_all, new, start)


def _decode_mlp(x, lp, cfg, comm: Comm, prefix: str = "") -> jax.Array:
    if cfg.mlp in ("swiglu", "geglu"):
        h = jnp.concatenate(
            [_in_proj(x, lp[prefix + "w_gate"], comm),
             _in_proj(x, lp[prefix + "w_up"], comm)], axis=-1)
    else:
        h = _in_proj(x, lp[prefix + "w_in"], comm)
    h = mlp_activation(cfg.mlp, h)
    return _row_parallel_out(h, lp[prefix + "w_out"], comm=comm,
                             shard_model=True)


def _decode_ssm(x, lp, cfg, comm: Comm, plan: TPPlan, state, conv_tail,
                prefix: str = "ssm_"):
    """x (b, d); state (b, H_loc, N, P); conv_tail (K-1, b, di_loc)."""
    def nm(s):
        return prefix + s

    di, h = cfg.ssm_d_inner, cfg.ssm_heads
    tp = comm.tp if plan.shard_ssm_heads else 1
    di_l, h_l = di // tp, h // tp
    zxdt = jnp.concatenate(
        [_in_proj(x, lp[nm("w_z")], comm),
         _in_proj(x, lp[nm("w_x")], comm),
         _in_proj(x, lp[nm("w_dt")], comm)], axis=-1)
    bc = _in_proj(x, lp[nm("w_bc")], comm)
    z, xs, dt_raw = jnp.split(zxdt, [di_l, 2 * di_l], axis=-1)
    b_t, c_t = jnp.split(bc, 2, axis=-1)
    g, n = cfg.ssm_groups, cfg.ssm_state

    # causal conv: roll the tail window
    conv_w = lp[nm("conv_w")]                      # (K, di_l)
    K = conv_w.shape[0]
    window = jnp.concatenate([conv_tail, xs[None]], axis=0)  # (K, b, di_l)
    xs_c = jnp.einsum("kbc,kc->bc", window.astype(jnp.float32),
                      conv_w.astype(jnp.float32)).astype(x.dtype)
    conv_tail = window[1:]
    xs_c = jax.nn.silu(xs_c.astype(jnp.float32)).astype(x.dtype)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + lp[nm("dt_bias")].astype(jnp.float32))
    state, y = ssd_decode_step(
        state, xs_c.reshape(-1, h_l, cfg.ssm_headdim), dt,
        lp[nm("a_log")], b_t.reshape(-1, g, n), c_t.reshape(-1, g, n),
        lp[nm("d_skip")])
    y = y.reshape(-1, di_l)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    yf = y.astype(jnp.float32)
    ssq = (yf * yf).sum(axis=-1, keepdims=True)
    denom = di_l
    if plan.shard_ssm_heads:
        ssq = comm.psum_model(ssq)
        denom = di
    yf = yf * jax.lax.rsqrt(ssq / denom + 1e-6)
    y = (yf * lp[nm("norm_w")].astype(jnp.float32)).astype(x.dtype)
    out = _row_parallel_out(y, lp[nm("w_out")], comm=comm,
                            shard_model=plan.shard_ssm_heads)
    return out, state, conv_tail


# ---------------------------------------------------------------------------
# serve_step
# ---------------------------------------------------------------------------

def make_serve_step(cfg: ModelConfig, comm: Optional[Comm] = None, *,
                    joint_kv: bool = False):
    """Build ``serve_step(params, cache, tokens) -> (next_tokens, cache')``.

    tokens: (b,) int32 — the tokens decoded at position ``cache.length``;
    returns greedily sampled next tokens and the updated cache.
    ``joint_kv``: shard the KV seq dim over data AND model (B == 1 long-
    context shapes).
    """
    comm = comm or local_comm()

    def serve_step(params, cache: DecodeCache, tokens: jax.Array):
        plan = tp_plan(cfg, comm.tp)
        pos = cache.length
        x = _embed_flat(tokens, comm.weight(params["emb"], fsdp_axis=1),
                        comm, scale=cfg.name.startswith("gemma"))

        is_vlm = cfg.family == "vlm"
        n_cross = _n_cross(cfg)
        lat = cache.latent
        per = (cfg.cross_attn_every - 1) if is_vlm else 0

        def layer(carry, scanned, dense=False):
            # ``dense``: a MoE model's leading dense layer, run before
            # the scan
            xc, kall, vall, sall, call_, load = carry
            idx, lp = scanned["idx"], scanned["lp"]
            row = None
            aux_kv = scanned.get("xlp")
            h = apply_norm(cfg.norm, xc, lp.get("norm1"), cfg.norm_eps)
            window = layer_window(cfg, idx) if cfg.sliding_window else 0

            with jax.named_scope(CACHE_IO):
                st = sall[idx] if sall is not None else None
                ct = call_[idx] if call_ is not None else None

            if cfg.family == "ssm":
                out, st, ct = _decode_ssm(h, lp, cfg, comm, plan, st, ct)
                xc = xc + out
            elif cfg.family == "hybrid":
                a_out, kall, vall = _decode_attn_layer(
                    h, lp, cfg, comm, plan, kall, vall, idx, pos, window,
                    joint_kv=joint_kv)
                s_out, st, ct = _decode_ssm(h, lp, cfg, comm, plan, st, ct)
                mix = 0.5 * (rms_norm(a_out, lp["mix_norm_a"])
                             + rms_norm(s_out, lp["mix_norm_s"]))
                xc = xc + mix
                h2 = apply_norm(cfg.norm, xc, lp.get("norm2"))
                xc = xc + _decode_mlp(h2, lp, cfg, comm)
            else:
                if cfg.is_mla:
                    a_out, row = _decode_mla(h, lp, cfg, comm, cache.latent,
                                             idx, pos, joint_kv=joint_kv)
                else:
                    a_out, kall, vall = _decode_attn_layer(
                        h, lp, cfg, comm, plan, kall, vall, idx, pos, window,
                        joint_kv=joint_kv)
                if cfg.parallel_block:
                    xc = xc + a_out + _decode_mlp(h, lp, cfg, comm)
                else:
                    xc = xc + a_out
                    if cfg.is_encdec and aux_kv is not None:
                        hx = rms_norm(xc, lp["normx"])
                        x_out, _, _ = _decode_attn_layer(
                            hx, lp, cfg, comm, plan, None, None, None, pos, 0,
                            joint_kv=joint_kv, prefix="x_",
                            memory_kv=aux_kv)
                        xc = xc + x_out
                    h2 = apply_norm(cfg.norm, xc, lp.get("norm2"),
                                    cfg.norm_eps)
                    if cfg.family == "moe" and not dense:
                        xc, load = _decode_moe(xc, h2, lp, load,
                                               idx - cfg.first_dense_layers)
                    else:
                        xc = xc + _decode_mlp(h2, lp, cfg, comm)

            with jax.named_scope(CACHE_IO):
                if sall is not None and st is not None:
                    sall = sall.at[idx].set(st)
                    call_ = call_.at[idx].set(ct)
            return (xc, kall, vall, sall, call_, load), row

        def _decode_moe(xc, h2, lp, load, j):
            """Dropless routed experts (the gather path for their weights)
            plus the shared expert; the held experts' load is added to
            row ``j`` of the counter."""
            mo, n = moe_decode(h2, lp, cfg, comm)
            if cfg.shared_expert_ff:
                mo = mo + _decode_mlp(h2, lp, cfg, comm, prefix="shared_")
            load = load.at[0, j].add(n[0]).at[1, j].max(n[0]) \
                .at[2, j].add(n[1])
            return xc + mo, load

        carry = (x, cache.k, cache.v, cache.ssm_state, cache.conv_tail,
                 cache.expert_load)
        dense_rows = []
        for i in range(cfg.first_dense_layers):
            # a constant index makes XLA copy the layer's latent slice
            # out (a whole layer a step); through a barrier it is read in
            # place, as in the loop
            carry, row = layer(carry, {
                "idx": jax.lax.optimization_barrier(jnp.int32(i)),
                "lp": jax.tree_util.tree_map(lambda a: a[i],
                                             params["dense_layers"])},
                dense=True)
            dense_rows.append(row)
        L_self = (cfg.n_layers - n_cross if is_vlm
                  else cfg.n_stacked_layers)
        idxs = jnp.arange(L_self, dtype=jnp.int32)
        if cfg.first_dense_layers:
            idxs = idxs + cfg.first_dense_layers
        scanned = {"idx": idxs, "lp": params["layers"]}
        if cfg.is_encdec:
            def layer_encdec(c, sl):
                idx, lp, xk, xv = sl
                return layer(c, {"idx": idx, "lp": lp,
                                 "xlp": (xk, xv)})
            carry, _ = jax.lax.scan(
                layer_encdec, carry,
                (scanned["idx"], params["layers"], cache.cross_k,
                 cache.cross_v))
        elif is_vlm:
            stack = jax.tree_util.tree_map(
                lambda a: a.reshape((n_cross, per) + a.shape[1:]),
                params["layers"])

            def superblock(c, sl):
                sb_idx, self_lp, cross_lp, xk, xv = sl

                def inner(c2, sl2):
                    j, lp2 = sl2
                    return layer(c2, {"idx": sb_idx * per + j, "lp": lp2})
                c, _ = jax.lax.scan(
                    inner, c, (jnp.arange(per, dtype=jnp.int32), self_lp))
                xc = c[0]
                hx = rms_norm(xc, cross_lp["normx"])
                x_out, _, _ = _decode_attn_layer(
                    hx, cross_lp, cfg, comm, tp_plan(cfg, comm.tp), None,
                    None, None, pos, 0, joint_kv=joint_kv, prefix="x_",
                    memory_kv=(xk, xv))
                xc = xc + jnp.tanh(cross_lp["gate_attn"]).astype(xc.dtype) \
                    * x_out
                hm = rms_norm(xc, cross_lp["normm"])
                ff = _decode_mlp(hm, cross_lp, cfg, comm, prefix="xm_")
                xc = xc + jnp.tanh(cross_lp["gate_mlp"]).astype(xc.dtype) \
                    * ff
                return (xc,) + c[1:], ()

            carry, _ = jax.lax.scan(
                superblock, carry,
                (jnp.arange(n_cross, dtype=jnp.int32), stack,
                 params["cross_layers"], cache.cross_k, cache.cross_v))
        else:
            def layer_plain(c, sl):
                idx, lp = sl
                return layer(c, {"idx": idx, "lp": lp})
            carry, rows = jax.lax.scan(layer_plain, carry,
                                       (scanned["idx"], params["layers"]))
            if cfg.is_mla:
                lat = _write_latent_rows(
                    cache.latent, jnp.concatenate(
                        [jnp.stack(dense_rows), rows]) if dense_rows
                    else rows, pos, comm, joint_kv=joint_kv)

        xc, kall, vall, sall, call_, load = carry
        xc = apply_norm("rmsnorm" if cfg.norm == "rmsnorm" else "layernorm",
                        xc, params["final_norm"], cfg.norm_eps)
        head = comm.weight(params.get("lm_head", params["emb"]),
                           fsdp_axis=1)
        logits = lm_head_logits(xc, head, comm, real_vocab=cfg.vocab)
        next_tokens = greedy_sample(logits, comm)
        blocks = cache.latent_blocks
        if cfg.is_mla:
            # every layer reads the same live blocks of its rows
            from repro.kernels.latent_decode.ops import blocks_read
            shard_len = cache.latent.shape[2]
            axes, rel = _latent_shard(pos, shard_len, comm,
                                      joint_kv=joint_kv)
            blocks = blocks + cache.latent.shape[0] * _psum_axes(
                blocks_read(jnp.clip(rel, 0, shard_len), shard_len), axes)
        new_cache = DecodeCache(k=kall, v=vall, ssm_state=sall,
                                conv_tail=call_, cross_k=cache.cross_k,
                                cross_v=cache.cross_v, length=pos + 1,
                                latent=lat, expert_load=load,
                                latent_blocks=blocks)
        return next_tokens, new_cache

    return serve_step


def precompute_cross_kv(params, memory: jax.Array, cfg: ModelConfig,
                        comm: Optional[Comm] = None
                        ) -> Tuple[jax.Array, jax.Array]:
    """Project encoder/image memory through every cross-attn layer's K/V.

    memory: (T, b, d) full-length (replicated over model).  Returns
    (cross_k, cross_v): (L_cross, T, b, n_kv, dh) — computed once at
    admission, reused every decode step (the big prefill→decode win for
    enc-dec/VLM).
    """
    comm = comm or local_comm()
    dh = cfg.resolved_head_dim
    stack = (params["cross_layers"] if cfg.family == "vlm"
             else params["layers"])

    def one(lp):
        wk = comm.weight(lp["x_wk"], fsdp_axis=0)
        wv = comm.weight(lp["x_wv"], fsdp_axis=0)
        k = jnp.tensordot(memory, wk, axes=1)
        v = jnp.tensordot(memory, wv, axes=1)
        k = k.reshape(*k.shape[:-1], -1, dh)
        v = v.reshape(*v.shape[:-1], -1, dh)
        return k, v

    # lax.map (scan) rather than vmap: collectives inside the body follow
    # the proven scan path, no batching rules involved
    return jax.lax.map(one, stack)


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ModelConfig, comm: Optional[Comm] = None):
    """Build ``prefill(params, batch) -> (last_hidden (b,d), logits_local)``.

    The prefill cell exercises the full-sequence forward at inference
    (no loss, last-position head).  Cache *population* for the serving
    engine's host path reuses the training forward's KV computation; the
    dry-run measures the compute/comm of the forward itself.
    """
    comm = comm or local_comm()

    def prefill(params, batch):
        x, _ = lm_mod.forward(params, batch, cfg, comm, remat=False)
        last = x[-1]                                   # (b, d)
        head = params.get("lm_head", params["emb"])
        head = comm.weight(head, fsdp_axis=1)
        logits = lm_head_logits(last, head, comm, real_vocab=cfg.vocab)
        return greedy_sample(logits, comm), last

    return prefill
