"""Mixture-of-Experts — MoE dispatch as an LCI active-message system.

The mapping (DESIGN.md §4, the *fullest* use of the paper's machinery):

* a token choosing expert ``e`` posts an **active message** whose *tag* is
  the expert id and whose *target rank* is the EP shard owning ``e``;
* the **matching engine** is the token→(expert, slot) assignment — the
  hash-bucket insert becomes a vectorized rank-in-expert computation;
* **packet-pool capacity slots**: each expert exposes ``capacity`` fixed
  slots per source rank (pre-registered packets); a token that finds the
  pool exhausted gets ``retry`` — here: it is *dropped* into the overflow
  ledger (the **backlog queue** analogue) and rides the residual stream;
* the **all-to-all** is the progress engine flushing aggregated messages
  (chunked over channels in LCI modes for compute overlap);
* the **combine** is the completion: each token's synchronizer joins its
  top-k expert replies weighted by router probabilities.

Experts are sharded over the ``model`` axis (EP == TP axis, standard for
MoE at TP≤experts); expert weights are additionally FSDP-sharded over
``data`` at rest.

A program may hold a share of the routed experts
(``ModelConfig.experts_held``, from ``first_expert``): the router scores
all ``n_experts``, and the layer returns the part of the result that its
own experts give (expert parallelism without the exchange).  Capacity
(and so dropping) applies to training's :func:`moe_block` only; decode
goes through :func:`moe_decode`, which is dropless.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import ModelConfig, ParamFactory
from .layers import mlp_activation


#: ``jax.named_scope`` names: the router, the selection and the weights;
#: the held experts' products
MOE_ROUTE = "moe_route"
MOE_EXPERTS = "moe_experts"


def init_moe(pf: ParamFactory, cfg: ModelConfig, stacked_layers: int = 0
             ) -> Dict[str, jax.Array]:
    d, e, ff = cfg.d_model, cfg.n_experts_held, cfg.d_ff
    mult = 2 if cfg.mlp in ("swiglu", "geglu") else 1
    L = (stacked_layers,) if stacked_layers else ()
    st = bool(stacked_layers)
    p = {
        "router": pf.dense("router", L + (d, cfg.n_experts), tp_axis=None,
                           fsdp_axis=0, stacked=st, scale=0.1),
        # expert weights: EP on the expert dim, FSDP on d_model
        "we_in": pf.dense("we_in", L + (e, d, mult * ff), tp_axis=0,
                          fsdp_axis=1, stacked=st),
        "we_out": pf.dense("we_out", L + (e, ff, d), tp_axis=0,
                           fsdp_axis=2, stacked=st),
    }
    if cfg.router_score == "sigmoid":
        p["router_bias"] = pf.zeros("router_bias", L + (cfg.n_experts,),
                                    stacked=st, dtype=jnp.float32)
    return p


def router_topk(logits: jax.Array, cfg: ModelConfig,
                bias: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array, jax.Array, Dict]:
    """Top-k routing with aux losses.

    logits: (T, E) fp32; ``bias`` (E,) is added to the scores to choose
    the experts and never weighs them (DeepSeek-V3's noaux_tc).  Returns
    (weights (T,k), experts (T,k) int32, probs (T,E), aux: dict of scalar
    losses/metrics).
    """
    if cfg.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        probs = scores / scores.sum(axis=-1, keepdims=True)
    else:
        scores = probs = jax.nn.softmax(logits, axis=-1)
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    _, experts = jax.lax.top_k(choice, cfg.top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    weights = weights / jnp.maximum(
        weights.sum(axis=-1, keepdims=True), 1e-9)        # renormalize top-k
    weights = weights * cfg.routed_scale
    # Switch-style load-balance loss over all k assignments
    e = logits.shape[-1]
    assign = jax.nn.one_hot(experts, e, dtype=jnp.float32).sum(axis=1)
    f = assign.mean(axis=0) * e / cfg.top_k               # dispatch fraction
    p_mean = probs.mean(axis=0) * e
    aux_lb = (f * p_mean).mean()
    lse = jax.nn.logsumexp(logits, axis=-1)
    aux_z = (lse * lse).mean()
    aux = {"aux_lb": aux_lb, "aux_z": aux_z}
    return weights.astype(jnp.float32), experts, probs, aux


def moe_block(x: jax.Array, p: Dict[str, jax.Array], cfg: ModelConfig,
              comm) -> Tuple[jax.Array, Dict]:
    """x: (s_local, b, d) pre-normed.  Returns (out (s_local, b, d), aux).

    Capacity per (expert, source-rank) = ceil(T·k/E · cf) rounded up to 8,
    where T is the *local* token count — fixed-size packet slots, so the
    a2a payload is static-shaped (a hard requirement under jit and exactly
    the paper's fixed-size pre-registered packet design).
    """
    s_l, b, d = x.shape
    t = s_l * b
    e, k = cfg.n_experts_held, cfg.top_k
    tp = comm.tp
    assert e % tp == 0, f"experts {e} must divide over model axis {tp}"

    xf = x.reshape(t, d)
    weights, experts, aux = _route(xf, p, cfg, comm)

    cap = int(-(-t * k // cfg.n_experts) * cfg.capacity_factor)
    cap = max(8, -(-cap // 8) * 8)                        # pad to 8

    # -- matching engine: slot assignment (position of each msg in its
    #    expert's packet queue), vectorized hash-bucket insert ------------
    flat_e = experts.reshape(t * k) - cfg.first_expert    # message tags
    held = (flat_e >= 0) & (flat_e < e)
    flat_e = jnp.where(held, flat_e, 0)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)   # (T·k, E_held)
    onehot = onehot * held[:, None]
    pos = jnp.cumsum(onehot, axis=0) - onehot             # rank within expert
    pos = (pos * onehot).sum(axis=-1)                     # (T·k,)
    keep = held & (pos < cap)                             # packet available?
    dropped = (held & ~keep).sum()                        # backlog ledger
    aux["dropped_frac"] = dropped.astype(jnp.float32) / (t * k)

    # -- stage payloads into packet slots: (E, cap, d) ---------------------
    slot_e = jnp.where(keep, flat_e, 0)
    slot_p = jnp.where(keep, pos, 0)
    payload = jnp.repeat(xf, k, axis=0)                   # (T·k, d)
    payload = jnp.where(keep[:, None], payload, 0).astype(x.dtype)
    dispatch = jnp.zeros((e, cap, d), x.dtype)
    dispatch = dispatch.at[slot_e, slot_p].add(payload)

    # -- progress: flush aggregated messages (all-to-all over EP axis) -----
    recv = comm.a2a(dispatch, split_axis=0, concat_axis=1)  # (E_l, cap·tp, d)

    # -- expert compute (grouped matmul over local experts) ----------------
    we_in = comm.weight(p["we_in"], fsdp_axis=1)          # (E_l, d, m·ff)
    we_out = comm.weight(p["we_out"], fsdp_axis=2)        # (E_l, ff, d)
    h = jnp.einsum("ecd,edf->ecf", recv, we_in,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    h = mlp_activation(cfg.mlp, h)
    out = jnp.einsum("ecf,efd->ecd", h, we_out,
                     preferred_element_type=jnp.float32).astype(x.dtype)

    # -- completion: return replies, combine with synchronizer weights -----
    back = comm.a2a(out, split_axis=1, concat_axis=0)     # (E, cap, d)
    gathered = back.reshape(e * cap, d)[slot_e * cap + slot_p]
    gathered = jnp.where(keep[:, None], gathered, 0)
    combined = (gathered.reshape(t, k, d).astype(jnp.float32)
                * weights[..., None]).sum(axis=1)
    return combined.reshape(s_l, b, d).astype(x.dtype), aux


def _route(xf: jax.Array, p: Dict[str, jax.Array], cfg: ModelConfig, comm):
    """Router logits over all ``n_experts`` for tokens xf (T, d), then
    :func:`router_topk`."""
    router_w = comm.weight(p["router"], fsdp_axis=0)
    logits = jnp.tensordot(xf.astype(jnp.float32),
                           router_w.astype(jnp.float32), axes=1)
    weights, experts, _, aux = router_topk(logits, cfg,
                                           p.get("router_bias"))
    return weights, experts, aux


def moe_decode(x: jax.Array, p: Dict[str, jax.Array], cfg: ModelConfig,
               comm) -> Tuple[jax.Array, jax.Array]:
    """Dropless routed experts for decode.  x: (b, d) pre-normed and
    replicated over the model axis.  Returns (out (b, d), load (2,
    E_held) int32): per held expert, the tokens the router sent it and
    the tokens its product combined (equal: nothing is capped).

    Every held expert's product runs over all b rows, and the combine
    weight (zero for a row that did not pick the expert) selects: no
    capacity, no slot assignment, nothing dropped.  At decode's row
    counts the expert weights are read once either way, and the product
    is near the chip's balance of FLOPs to bytes.  Under expert
    parallelism over the model axis each rank computes its own experts
    and the parts are summed."""
    e, k = cfg.n_experts_held, cfg.top_k
    tp = comm.tp
    assert e % tp == 0, f"experts {e} must divide over model axis {tp}"
    e_l = e // tp
    with jax.named_scope(MOE_ROUTE):
        weights, experts, _ = _route(x, p, cfg, comm)
        local = experts - cfg.first_expert                 # (b, k)
        pick = jax.nn.one_hot(local, e, dtype=jnp.float32)  # 0 off-share
        comb = (pick * weights[..., None]).sum(axis=1)     # (b, E_held)
        used = pick.sum(axis=1) > 0
        load = jnp.stack([pick.sum(axis=(0, 1)).astype(jnp.int32),
                          used.sum(axis=0).astype(jnp.int32)])
        if tp > 1:
            comb = jax.lax.dynamic_slice_in_dim(
                comb, comm.model_index() * e_l, e_l, axis=1)
    with jax.named_scope(MOE_EXPERTS):
        we_in = comm.weight(p["we_in"], fsdp_axis=1)      # (E_l, d, m·ff)
        we_out = comm.weight(p["we_out"], fsdp_axis=2)    # (E_l, ff, d)
        h = jnp.einsum("bd,edf->ebf", x, we_in,
                       preferred_element_type=jnp.float32).astype(x.dtype)
        h = mlp_activation(cfg.mlp, h)
        y = jnp.einsum("ebf,efd->ebd", h, we_out,
                       preferred_element_type=jnp.float32)
        out = jnp.einsum("ebd,be->bd", y, comb)
        if tp > 1:
            out = comm.psum_model(out)
    return out.astype(x.dtype), load
