"""Transformer blocks: TP plans, attention ops, MLP/MoE wiring.

Tensor-parallel **plans** (DESIGN.md §4):

* Plan A (``shard_heads``) — q heads sharded over the model axis; entered
  with ``ag_matmul`` (full seq × local heads), exited with ``matmul_rs``.
  KV: sharded too when ``n_kv % tp == 0``; otherwise the KV projection is
  replicated (tiny: ``2·n_kv·dh`` wide) and each rank computes full KV.
* Plan B (``replicated heads``) — for archs whose head counts do not divide
  the model axis (gemma3: 4, hymba: 25, whisper: 6).  q is computed for the
  *local sequence rows only* (no gather), K/V are projected locally and
  ring-allgathered; attention has zero redundant FLOPs and the only
  collective is the small KV gather.  Weights are replicated over model
  (all these archs are <2B params) and FSDP-sharded over data at rest.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from .attention import flash_attention
from .common import ModelConfig, ParamFactory, shard_decisions
from .layers import apply_norm, apply_rope, mlp_activation, mlp_block, rms_norm


@dataclasses.dataclass(frozen=True)
class TPPlan:
    tp: int
    shard_heads: bool
    shard_kv: bool
    shard_ssm_heads: bool

    def q_local(self, cfg: ModelConfig) -> int:
        return cfg.n_heads // self.tp if self.shard_heads else cfg.n_heads

    def kv_local(self, cfg: ModelConfig) -> int:
        return cfg.n_kv_heads // self.tp if self.shard_kv else cfg.n_kv_heads


def tp_plan(cfg: ModelConfig, tp: int) -> TPPlan:
    dec = shard_decisions(cfg)
    if dec["attn"] and tp > 1:
        assert cfg.n_heads % tp == 0, \
            f"{cfg.name}: heads {cfg.n_heads} sharded at init but tp={tp}"
    if dec["ssm"] and tp > 1:
        assert cfg.ssm_heads % tp == 0
    return TPPlan(tp=tp, shard_heads=dec["attn"], shard_kv=dec["kv"],
                  shard_ssm_heads=dec["ssm"])


# ---------------------------------------------------------------------------
# parameter initialization for one attention + MLP block
# ---------------------------------------------------------------------------

def init_attention(pf: ParamFactory, cfg: ModelConfig, prefix: str = "",
                   stacked_layers: int = 0) -> Dict[str, jax.Array]:
    """Weights for one attention op (shapes are GLOBAL; sharding comes from
    the recorded ParamSpecs).  ``stacked_layers``>0 prepends an L dim."""
    d, dh = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    L = (stacked_layers,) if stacked_layers else ()
    st = bool(stacked_layers)
    dec = shard_decisions(cfg)
    a_shard, kv_shard = dec["attn"], dec["kv"]
    # K and V are stored as SEPARATE params: TP shards each on its own head
    # dim, and the use site concatenates the *local* shards — a fused
    # global [K|V] matrix sharded on the fused dim would hand each rank a
    # slice crossing the K/V boundary (see tests/test_distributed.py).
    p = {
        prefix + "wq": pf.dense(prefix + "wq", L + (d, nq * dh),
                                tp_axis=1 if a_shard else None,
                                fsdp_axis=0, stacked=st),
        prefix + "wk": pf.dense(prefix + "wk", L + (d, nkv * dh),
                                tp_axis=1 if kv_shard else None,
                                fsdp_axis=0, stacked=st),
        prefix + "wv": pf.dense(prefix + "wv", L + (d, nkv * dh),
                                tp_axis=1 if kv_shard else None,
                                fsdp_axis=0, stacked=st),
        prefix + "wo": pf.dense(prefix + "wo", L + (nq * dh, d),
                                tp_axis=0 if a_shard else None,
                                fsdp_axis=1, stacked=st),
    }
    if cfg.qk_norm:
        p[prefix + "q_norm"] = pf.ones(prefix + "q_norm", L + (dh,),
                                       stacked=st)
        p[prefix + "k_norm"] = pf.ones(prefix + "k_norm", L + (dh,),
                                       stacked=st)
    return p


def init_mlp(pf: ParamFactory, cfg: ModelConfig, prefix: str = "",
             stacked_layers: int = 0, d_ff: Optional[int] = None
             ) -> Dict[str, jax.Array]:
    d = cfg.d_model
    ff = d_ff if d_ff is not None else cfg.d_ff
    L = (stacked_layers,) if stacked_layers else ()
    st = bool(stacked_layers)
    p = {
        prefix + "w_out": pf.dense(prefix + "w_out", L + (ff, d),
                                   tp_axis=0, fsdp_axis=1, stacked=st),
    }
    if cfg.mlp in ("swiglu", "geglu"):
        # gate and up stored separately (same boundary argument as K/V)
        p[prefix + "w_gate"] = pf.dense(prefix + "w_gate", L + (d, ff),
                                        tp_axis=1, fsdp_axis=0,
                                        stacked=st)
        p[prefix + "w_up"] = pf.dense(prefix + "w_up", L + (d, ff),
                                      tp_axis=1, fsdp_axis=0, stacked=st)
    else:
        p[prefix + "w_in"] = pf.dense(prefix + "w_in", L + (d, ff),
                                      tp_axis=1, fsdp_axis=0, stacked=st)
    return p


# ---------------------------------------------------------------------------
# attention op (training/prefill; decode lives in repro.serving.engine)
# ---------------------------------------------------------------------------

def attention_op(x: jax.Array, p: Dict[str, jax.Array], cfg: ModelConfig,
                 comm, plan: TPPlan, *, window: int, q_offset,
                 memory: Optional[jax.Array] = None,
                 causal: bool = True, prefix: str = "") -> jax.Array:
    """x: (s_local, b, d) pre-normed; returns (s_local, b, d) un-residual.

    ``memory``: (t, b, d) full-length cross-attention source (replicated
    over the model axis) — when given, K/V come from it and masks are off.
    """
    dh = cfg.resolved_head_dim
    wq = comm.weight(p[prefix + "wq"], fsdp_axis=0)
    # concat of LOCAL shards: layout is [K_local | V_local] by construction
    wkv = jnp.concatenate(
        [comm.weight(p[prefix + "wk"], fsdp_axis=0),
         comm.weight(p[prefix + "wv"], fsdp_axis=0)], axis=1)
    wo = comm.weight(p[prefix + "wo"], fsdp_axis=1)
    kv_src = memory if memory is not None else x
    is_cross = memory is not None
    nq_l, nkv_l = plan.q_local(cfg), plan.kv_local(cfg)

    if plan.shard_heads:
        # Plan A: full-seq q for the local head shard.
        q = comm.ag_matmul(x, wq)                       # (s, b, nq_l*dh)
        if plan.shard_kv and not is_cross:
            kv = comm.ag_matmul(x, wkv)                 # (s, b, 2*nkv_l*dh)
            k, v = jnp.split(kv.reshape(*kv.shape[:-1], 2 * nkv_l, dh), 2,
                             axis=-2)
        else:
            # replicated KV projection: every rank computes ALL kv heads,
            # then slices the contiguous kv-head range its GLOBAL q heads
            # map to (GQA grouping is global, not local).
            kv_loc = jnp.tensordot(kv_src, wkv, axes=1)
            kv = kv_loc if is_cross else comm.ag_seq(kv_loc)
            kv = kv.reshape(*kv.shape[:-1], 2, nkv_l, dh)
            g_ratio = cfg.n_heads // cfg.n_kv_heads
            if nq_l >= g_ratio:
                assert nq_l % g_ratio == 0, (nq_l, g_ratio)
                cnt = nq_l // g_ratio
            else:
                assert g_ratio % nq_l == 0, (nq_l, g_ratio)
                cnt = 1
            rank = comm.model_index()
            start = (rank * nq_l) // g_ratio
            kv = jax.lax.dynamic_slice_in_dim(kv, start * jnp.int32(1),
                                              cnt, axis=-2)
            k, v = kv[..., 0, :, :], kv[..., 1, :, :]
        s_full = q.shape[0]
        q = q.reshape(s_full, *q.shape[1:-1], nq_l, dh)
        q_off_attn = 0                                  # q covers full seq
    else:
        # Plan B: local-seq q, all heads; KV gathered.
        q = jnp.tensordot(x, wq, axes=1)                # (s_l, b, nq*dh)
        kv_loc = jnp.tensordot(kv_src, wkv, axes=1)
        kv = kv_loc if is_cross else comm.ag_seq(kv_loc)
        q = q.reshape(*q.shape[:-1], nq_l, dh)
        k, v = jnp.split(kv.reshape(*kv.shape[:-1], 2 * nkv_l, dh), 2,
                         axis=-2)
        q_off_attn = q_offset

    if cfg.qk_norm:
        q = rms_norm(q, p[prefix + "q_norm"])
        k = rms_norm(k, p[prefix + "k_norm"])
    if not is_cross:                                    # RoPE (self-attn only)
        q_pos = q_off_attn + jnp.arange(q.shape[0], dtype=jnp.int32)
        k_pos = jnp.arange(k.shape[0], dtype=jnp.int32)
        q = apply_rope(q, q_pos, cfg.rope_theta)
        k = apply_rope(k, k_pos, cfg.rope_theta)

    o = flash_attention(q, k, v, causal=causal and not is_cross,
                        window=0 if is_cross else window,
                        q_offset=q_off_attn)
    o = o.reshape(*o.shape[:-2], nq_l * dh)

    if plan.shard_heads:
        return comm.matmul_rs(o, wo)                    # (s_l, b, d)
    return jnp.tensordot(o, wo, axes=1)                 # already local rows


def layer_window(cfg: ModelConfig, layer_idx) -> jax.Array:
    """Effective attention window for layer ``layer_idx`` (traced ok).

    The global/local pattern (gemma3 5:1, hymba's explicit global layers)
    becomes *data*: a huge window == global attention, so the scan body has
    one code path and one collective schedule for every layer."""
    if cfg.sliding_window == 0:
        return jnp.int32(0)
    is_global = jnp.zeros((), bool)
    if cfg.swa_every_nth_global:
        is_global |= (layer_idx + 1) % cfg.swa_every_nth_global == 0
    for g in cfg.global_layers:
        is_global |= layer_idx == g
    return jnp.where(is_global, jnp.int32(1 << 30),
                     jnp.int32(cfg.sliding_window))


def swa_attention_op(x, p, cfg, comm, plan, *, layer_idx, q_offset,
                     prefix: str = "") -> jax.Array:
    """Attention with the per-layer global/local pattern."""
    w = layer_window(cfg, layer_idx) if cfg.sliding_window else 0
    return attention_op(x, p, cfg, comm, plan, window=w,
                        q_offset=q_offset, prefix=prefix)
