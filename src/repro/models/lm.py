"""Full language models: init + loss for every assigned architecture family.

Layer stacks are ``lax.scan``-rolled over stacked (L, ...) parameter
pytrees so that HLO size and compile time are O(1) in depth — a 104B-param
64-layer config compiles the same program as a 4-layer smoke config.  The
scan body is optionally ``jax.checkpoint``-ed (remat) for activation
memory.  All data movement inside blocks goes through ``Comm`` (LCI-X).

Batch convention (seq-major local view):
    tokens  (s_local, b)   int32
    labels  (s_local, b)   int32   (-100 = ignore)
    [frames (t_local, b, d)]        audio stub (whisper)
    [image_embeds (ti, b, d)]       vision stub (llama-3.2-vision)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.comm import Comm
from .attention import flash_attention
from .blocks import (TPPlan, attention_op, init_attention, init_mlp,
                     layer_window, swa_attention_op, tp_plan)
from .common import ModelConfig, ParamFactory, ParamSpec
from .layers import (apply_norm, apply_rope_pairs, embed_tokens,
                     lm_head_loss, mlp_block, rms_norm, sinusoidal_positions)
from .moe import init_moe, moe_block
from .ssm import init_ssm, ssm_op


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_norm(pf: ParamFactory, cfg: ModelConfig, name: str, L: int):
    if cfg.norm == "layernorm_np":
        return {}                          # OLMo: non-parametric, no weight
    return {name: pf.ones(name, (L, cfg.d_model), stacked=True)}


#: latent attention's RMSNorm on the kv latent keeps DeepSeek's default
#: eps (its layer norms take the config's)
MLA_KV_NORM_EPS = 1e-6


def init_mla(pf: ParamFactory, cfg: ModelConfig, L: int
             ) -> Dict[str, jax.Array]:
    """Latent attention (DeepSeek-V2/V3, no q_lora_rank): ``wq`` d ->
    heads x (nope + rope); ``wkv_a`` d -> latent + shared rope key;
    ``kv_norm`` on the latent; ``wk_b`` and ``wv_b`` the per-head halves
    of DeepSeek's ``kv_b_proj`` (latent -> heads x nope, heads x v);
    ``wo`` heads x v -> d.  Replicated over the model axis (attention is
    data-parallel), FSDP over data."""
    d, nq, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq": pf.dense("wq", (L, d, nq * (dn + dr)), tp_axis=None,
                       fsdp_axis=0),
        "wkv_a": pf.dense("wkv_a", (L, d, r + dr), tp_axis=None,
                          fsdp_axis=0),
        "kv_norm": pf.ones("kv_norm", (L, r)),
        "wk_b": pf.dense("wk_b", (L, r, nq * dn), tp_axis=None, fsdp_axis=0),
        "wv_b": pf.dense("wv_b", (L, r, nq * dv), tp_axis=None, fsdp_axis=0),
        "wo": pf.dense("wo", (L, nq * dv, d), tp_axis=None, fsdp_axis=1),
    }


def mla_attention_op(x, p, cfg: ModelConfig, comm: Comm, *, q_offset
                     ) -> jax.Array:
    """Latent attention in its full (expanded) form, for training and
    prefill.  x (s_local, b, d) pre-normed; queries for the local rows,
    the latent and rope key gathered over the sequence (Plan B's
    schedule: the gathered row is r + rope wide)."""
    nq, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    s_l, b, _ = x.shape
    q = jnp.tensordot(x, comm.weight(p["wq"], fsdp_axis=0), axes=1)
    q = q.reshape(s_l, b, nq, dn + dr)
    kv_a = comm.ag_seq(jnp.tensordot(
        x, comm.weight(p["wkv_a"], fsdp_axis=0), axes=1))  # (s, b, r+dr)
    s = kv_a.shape[0]
    c = rms_norm(kv_a[..., :r], p["kv_norm"], eps=MLA_KV_NORM_EPS)
    k_pe = apply_rope_pairs(kv_a[..., None, r:],
                            jnp.arange(s, dtype=jnp.int32), cfg.rope_theta)
    k_nope = jnp.tensordot(c, comm.weight(p["wk_b"], fsdp_axis=0),
                           axes=1).reshape(s, b, nq, dn)
    v = jnp.tensordot(c, comm.weight(p["wv_b"], fsdp_axis=0),
                      axes=1).reshape(s, b, nq, dv)
    q_pe = apply_rope_pairs(q[..., dn:], q_offset + jnp.arange(
        s_l, dtype=jnp.int32), cfg.rope_theta)
    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (s, b, nq, dr))],
                        axis=-1)
    o = flash_attention(q, k, v, causal=True, q_offset=q_offset,
                        scale=(dn + dr) ** -0.5)
    return jnp.tensordot(o.reshape(s_l, b, nq * dv),
                         comm.weight(p["wo"], fsdp_axis=1), axes=1)


def _init_layer_stack(pf: ParamFactory, cfg: ModelConfig, L: int,
                      *, causal_attn: bool = True, dense: bool = False
                      ) -> Dict[str, jax.Array]:
    """One homogeneous stack of L layers for the config's family;
    ``dense``: a leading dense layer of a MoE model (its FFN of width
    ``dense_ff`` in place of the experts)."""
    p: Dict[str, jax.Array] = {}
    p.update(_init_norm(pf, cfg, "norm1", L))
    if cfg.is_mla:
        p.update(init_mla(pf, cfg, L))
    elif cfg.family in ("dense", "moe", "vlm", "audio", "hybrid"):
        p.update(init_attention(pf, cfg, stacked_layers=L))
    if cfg.family in ("ssm", "hybrid"):
        p.update(init_ssm(pf, cfg, stacked_layers=L))
    if cfg.family == "hybrid":
        p["mix_norm_a"] = pf.ones("mix_norm_a", (L, cfg.d_model),
                                  stacked=True)
        p["mix_norm_s"] = pf.ones("mix_norm_s", (L, cfg.d_model),
                                  stacked=True)
    if dense:
        p.update(_init_norm(pf, cfg, "norm2", L))
        p.update(init_mlp(pf, cfg, stacked_layers=L, d_ff=cfg.dense_ff))
    elif cfg.family == "moe":
        p.update(_init_norm(pf, cfg, "norm2", L))
        p.update(init_moe(pf, cfg, stacked_layers=L))
        if cfg.shared_expert_ff:
            p.update(init_mlp(pf, cfg, prefix="shared_", stacked_layers=L,
                              d_ff=cfg.shared_expert_ff))
    elif cfg.family != "ssm" and cfg.d_ff and not cfg.parallel_block:
        p.update(_init_norm(pf, cfg, "norm2", L))
        p.update(init_mlp(pf, cfg, stacked_layers=L))
    elif cfg.parallel_block and cfg.d_ff:
        p.update(init_mlp(pf, cfg, stacked_layers=L))   # shares norm1
    return p


def init_params(cfg: ModelConfig, key: jax.Array
                ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Returns (params, specs) — parallel pytrees."""
    pf = ParamFactory(key, cfg.dtype)
    d = cfg.d_model
    params: Dict[str, Any] = {}
    specs: Dict[str, Any] = {}

    def grab(sub: Dict[str, jax.Array], dest_key: str):
        params[dest_key] = sub
        specs[dest_key] = {k: pf.specs[k] for k in sub}
        pf.specs.clear()

    # embedding: vocab (padded) TP-sharded, features FSDP-sharded
    params["emb"] = pf.dense("emb", (cfg.padded_vocab, d), tp_axis=0,
                             fsdp_axis=1, stacked=False, scale=1.0)
    specs["emb"] = pf.specs.pop("emb")
    if not cfg.tie_embeddings:
        params["lm_head"] = pf.dense("lm_head", (cfg.padded_vocab, d),
                                     tp_axis=0, fsdp_axis=1, stacked=False)
        specs["lm_head"] = pf.specs.pop("lm_head")
    params["final_norm"] = pf.ones("final_norm", (d,), stacked=False)
    specs["final_norm"] = pf.specs.pop("final_norm")

    if cfg.family == "vlm":
        n_cross = cfg.n_layers // cfg.cross_attn_every
        n_self = cfg.n_layers - n_cross
        grab(_init_layer_stack(pf, cfg, n_self), "layers")
        cp: Dict[str, jax.Array] = {}
        cp.update({"normx": pf.ones("normx", (n_cross, d), stacked=True)})
        cp.update(init_attention(pf, cfg, prefix="x_",
                                 stacked_layers=n_cross))
        cp["gate_attn"] = pf.zeros("gate_attn", (n_cross,), stacked=True,
                                   dtype=jnp.float32)
        cp.update({"normm": pf.ones("normm", (n_cross, d), stacked=True)})
        cp.update(init_mlp(pf, cfg, prefix="xm_", stacked_layers=n_cross))
        cp["gate_mlp"] = pf.zeros("gate_mlp", (n_cross,), stacked=True,
                                  dtype=jnp.float32)
        grab(cp, "cross_layers")
    elif cfg.is_encdec:
        grab(_init_layer_stack(pf, cfg, cfg.encoder_layers), "encoder")
        params["enc_final_norm"] = pf.ones("enc_final_norm", (d,),
                                           stacked=False)
        specs["enc_final_norm"] = pf.specs.pop("enc_final_norm")
        dp: Dict[str, jax.Array] = {}
        L = cfg.n_layers
        dp.update(_init_norm(pf, cfg, "norm1", L))
        dp.update(init_attention(pf, cfg, stacked_layers=L))
        dp.update({"normx": pf.ones("normx", (L, d), stacked=True)})
        dp.update(init_attention(pf, cfg, prefix="x_", stacked_layers=L))
        dp.update(_init_norm(pf, cfg, "norm2", L))
        dp.update(init_mlp(pf, cfg, stacked_layers=L))
        grab(dp, "layers")
    else:
        if cfg.first_dense_layers:
            grab(_init_layer_stack(pf, cfg, cfg.first_dense_layers,
                                   dense=True), "dense_layers")
        grab(_init_layer_stack(pf, cfg, cfg.n_stacked_layers), "layers")
    return params, specs


# ---------------------------------------------------------------------------
# blocks (scan bodies)
# ---------------------------------------------------------------------------

def _mlp_op(x, lp, cfg, comm, prefix: str = "") -> jax.Array:
    if cfg.mlp in ("swiglu", "geglu"):
        w_in = jnp.concatenate(
            [comm.weight(lp[prefix + "w_gate"], fsdp_axis=0),
             comm.weight(lp[prefix + "w_up"], fsdp_axis=0)], axis=1)
    else:
        w_in = comm.weight(lp[prefix + "w_in"], fsdp_axis=0)
    w_out = comm.weight(lp[prefix + "w_out"], fsdp_axis=1)
    return mlp_block(x, w_in, w_out, cfg.mlp, comm)


def _decoder_block(x, lp, idx, cfg: ModelConfig, comm: Comm, plan: TPPlan,
                   q_offset, memory=None, dense: bool = False
                   ) -> Tuple[jax.Array, Dict]:
    """One decoder layer of any family; returns (x', aux).  ``dense``: a
    MoE model's leading dense layer."""
    aux: Dict[str, jax.Array] = {}
    h = apply_norm(cfg.norm, x, lp.get("norm1"), cfg.norm_eps)

    if cfg.family == "ssm":
        return x + ssm_op(h, lp, cfg, comm, plan), aux

    if cfg.family == "hybrid":
        a_out = swa_attention_op(h, lp, cfg, comm, plan, layer_idx=idx,
                                 q_offset=q_offset)
        s_out = ssm_op(h, lp, cfg, comm, plan)
        mix = 0.5 * (rms_norm(a_out, lp["mix_norm_a"])
                     + rms_norm(s_out, lp["mix_norm_s"]))
        x = x + mix
        h2 = apply_norm(cfg.norm, x, lp.get("norm2"))
        return x + _mlp_op(h2, lp, cfg, comm), aux

    if cfg.is_mla:
        attn = mla_attention_op(h, lp, cfg, comm, q_offset=q_offset)
    else:
        attn = swa_attention_op(h, lp, cfg, comm, plan, layer_idx=idx,
                                q_offset=q_offset)
    if cfg.parallel_block:                       # Cohere: attn ∥ mlp
        return x + attn + _mlp_op(h, lp, cfg, comm), aux

    x = x + attn
    if memory is not None and "x_wq" in lp:      # enc-dec cross-attention
        hx = rms_norm(x, lp["normx"])
        x = x + attention_op(hx, lp, cfg, comm, plan, window=0,
                             q_offset=q_offset, memory=memory, prefix="x_")
    h2 = apply_norm(cfg.norm, x, lp.get("norm2"), cfg.norm_eps)
    if cfg.family == "moe" and not dense:
        moe_out, aux = moe_block(h2, lp, cfg, comm)
        if cfg.shared_expert_ff:
            moe_out = moe_out + _mlp_op(h2, lp, cfg, comm, prefix="shared_")
        return x + moe_out, aux
    return x + _mlp_op(h2, lp, cfg, comm), aux


def _cross_block(x, lp, cfg, comm, plan, q_offset, memory):
    """Gated cross-attention layer (llama-3.2-vision style)."""
    hx = rms_norm(x, lp["normx"])
    attn = attention_op(hx, lp, cfg, comm, plan, window=0,
                        q_offset=q_offset, memory=memory, prefix="x_")
    x = x + jnp.tanh(lp["gate_attn"]).astype(x.dtype) * attn
    hm = rms_norm(x, lp["normm"])
    ff = _mlp_op(hm, lp, cfg, comm, prefix="xm_")
    return x + jnp.tanh(lp["gate_mlp"]).astype(x.dtype) * ff


# ---------------------------------------------------------------------------
# forward / loss
# ---------------------------------------------------------------------------

_AUX_KEYS = ("aux_lb", "aux_z", "dropped_frac")


def _scan_stack(x, stack, cfg, comm, plan, q_offset, *, body, remat: bool,
                length: int):
    idxs = jnp.arange(length, dtype=jnp.int32)

    def fn(carry, sl):
        xc, aux_acc = carry
        idx, lp = sl
        xc, aux = body(xc, lp, idx)
        aux_acc = {k: aux_acc[k] + aux.get(k, 0.0) for k in _AUX_KEYS}
        return (xc, aux_acc), ()

    if remat:
        fn = jax.checkpoint(fn, prevent_cse=False)
    aux0 = {k: jnp.zeros((), jnp.float32) for k in _AUX_KEYS}
    (x, aux), _ = jax.lax.scan(fn, (x, aux0), (idxs, stack))
    return x, aux


def forward(params: Dict[str, Any], batch: Dict[str, jax.Array],
            cfg: ModelConfig, comm: Comm, *, remat: bool = True
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Returns (x_full (s, b, d) post-final-norm full-sequence, aux)."""
    plan = tp_plan(cfg, comm.tp)
    tokens = batch["tokens"]
    s_l, b = tokens.shape
    q_offset = comm.model_index() * s_l

    emb = comm.weight(params["emb"], fsdp_axis=1)
    x = embed_tokens(tokens, emb, comm,
                     scale_by_sqrt_dim=cfg.name.startswith("gemma"))

    memory = None
    if cfg.family == "vlm":
        memory = batch["image_embeds"]              # (ti, b, d) replicated
    if cfg.is_encdec:
        memory = _encode(params, batch, cfg, comm, plan, remat=remat)

    if cfg.family == "vlm":
        n_cross = cfg.n_layers // cfg.cross_attn_every
        per = cfg.cross_attn_every - 1              # self layers per block
        stack = jax.tree_util.tree_map(
            lambda a: a.reshape((n_cross, per) + a.shape[1:]),
            params["layers"])
        cstack = params["cross_layers"]
        mem = memory

        def superblock(xc, lp_pair, idx):
            self_lp, cross_lp = lp_pair

            def inner(xc2, sl):
                j, lp = sl
                y, _ = _decoder_block(xc2, lp, idx * per + j, cfg, comm,
                                      plan, q_offset)
                return y, ()
            xc, _ = jax.lax.scan(
                inner, xc, (jnp.arange(per, dtype=jnp.int32), self_lp))
            xc = _cross_block(xc, cross_lp, cfg, comm, plan, q_offset, mem)
            return xc, {}

        x, aux = _scan_stack(
            x, (stack, cstack), cfg, comm, plan, q_offset,
            body=lambda xc, lp, idx: superblock(xc, lp, idx),
            remat=remat, length=n_cross)
    else:
        mem = memory
        for i in range(cfg.first_dense_layers):
            lp = jax.tree_util.tree_map(lambda a: a[i],
                                        params["dense_layers"])
            x, _ = _decoder_block(x, lp, i, cfg, comm, plan, q_offset,
                                  dense=True)

        def body(xc, lp, idx):
            return _decoder_block(xc, lp, idx + cfg.first_dense_layers, cfg,
                                  comm, plan, q_offset, memory=mem)

        x, aux = _scan_stack(x, params["layers"], cfg, comm, plan,
                             q_offset, body=body, remat=remat,
                             length=cfg.n_stacked_layers)

    x = apply_norm("rmsnorm" if cfg.norm == "rmsnorm" else "layernorm",
                   x, params["final_norm"], cfg.norm_eps)
    x = comm.ag_seq(x)                              # full seq for the head
    n_layers = max(cfg.n_layers, 1)
    # aux terms (router losses) are computed from *local* tokens, so they
    # vary across the model axis; grad-exact-mean them so the total loss is
    # replicated (required for exact distributed gradients — see
    # Comm.psum_model_ge).
    tp = comm.tp
    aux = {k: comm.psum_model_ge(v / n_layers) / tp for k, v in aux.items()}
    return x, aux


def _encode(params, batch, cfg, comm, plan, *, remat: bool) -> jax.Array:
    """Whisper-style encoder over stub frame embeddings -> full memory."""
    frames = batch["frames"]                        # (t_local, b, d)
    t_l, b, d = frames.shape
    offset = comm.model_index() * t_l
    pos = sinusoidal_positions(t_l, d, offset=offset).astype(frames.dtype)
    x = frames + pos[:, None, :]

    def body(xc, lp, idx):
        h = apply_norm(cfg.norm, xc, lp.get("norm1"))
        attn = attention_op(h, lp, cfg, comm, plan, window=0, q_offset=0,
                            causal=False)
        xc = xc + attn
        h2 = apply_norm(cfg.norm, xc, lp.get("norm2"))
        return xc + _mlp_op(h2, lp, cfg, comm), {}

    x, _ = _scan_stack(x, params["encoder"], cfg, comm, plan, 0,
                       body=body, remat=remat, length=cfg.encoder_layers)
    x = apply_norm("rmsnorm" if cfg.norm == "rmsnorm" else "layernorm",
                   x, params["enc_final_norm"])
    return comm.ag_seq(x)                           # memory: (t, b, d)


def loss_and_metrics(params, batch, cfg: ModelConfig, comm: Comm, *,
                     remat: bool = True, loss_chunk: int = 1024
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Mean CE (+ router aux) over this data shard; caller pmean's."""
    x, aux = forward(params, batch, cfg, comm, remat=remat)
    labels = comm.ag_seq(batch["labels"])           # (s, b)
    head = params.get("lm_head", params["emb"])
    head = comm.weight(head, fsdp_axis=1)

    s = x.shape[0]
    ck = min(loss_chunk, s)
    while s % ck:
        ck -= 1
    nck = s // ck

    def chunk_loss(args):
        xb, lb = args
        return lm_head_loss(xb, head, lb, comm, real_vocab=cfg.vocab)

    sums, ns = jax.lax.map(
        chunk_loss, (x.reshape(nck, ck, *x.shape[1:]),
                     labels.reshape(nck, ck, *labels.shape[1:])))
    total, n = sums.sum(), ns.sum()
    ce = total / jnp.maximum(n, 1)
    loss = (ce + cfg.router_aux_coef * aux["aux_lb"]
            + cfg.router_z_coef * aux["aux_z"])
    metrics = {"loss": loss, "ce": ce, "ntok": n, **aux}
    return loss, metrics
