"""Plain Moonlight-16B-A3B forward (DeepSeek-V3 architecture,
huggingface.co/moonshotai/Moonlight-16B-A3B config.json) in float32 at
the highest matmul precision, independent of the program: no cache, no
batching, no kernels.

Layer l: x += MLA(RMSNorm(x)); x += FFN(RMSNorm(x)), RMSNorm eps 1e-5.
MLA (no q_lora_rank) in its expanded form: q = h Wq split per head into
128 "nope" and 64 rotary dims; [c, k_pe] = h Wkv_a; c is RMS-normed
(eps 1e-6, the modelling code's default for kv_a_layernorm); K_nope and
V are expanded from c through kv_b_proj (here its two per-head halves,
``wk_b`` and ``wv_b``); the 64-dim rotary key is shared by all 16 heads;
rotary (theta 50000, no scaling) in the pair order of DeepSeek-V3's
``apply_rotary_pos_emb`` (de-interleave, then rotate halves); softmax
scale 1/sqrt(192); causal.
FFN: layer 0 is dense SwiGLU of width 11264.  Layers 1-26 are MoE: sigmoid
scores of a 64-way router; the top 6 are chosen by score plus a
per-expert bias (noaux_tc with one group, so group selection is void),
weighed by the unbiased scores, renormalized over the 6 and scaled by
2.446; routed experts are SwiGLU of width 1408; the shared experts (2 of
1408) are one SwiGLU of width 2816, added to the routed part.
The head is untied from the embedding; the final norm is RMSNorm.

The one departure from the published model is the expert share: the
router scores all 64 experts and chooses 6, but only the held experts
(``n_routed_experts`` of them from ``first_held_expert``: 0-7, one chip
of an eight-way expert-parallel deployment) contribute; what the other
experts would add is left out here as in the program.

The weights are made here from the seed, in the layout the program takes
(``param_shapes``), and handed to both the program and this forward.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import weights

HI = jax.lax.Precision.HIGHEST
KV_NORM_EPS = 1e-6


def dims(m: dict) -> dict:
    return {"L": m["num_hidden_layers"], "Ld": m["first_k_dense_replace"],
            "d": m["hidden_size"], "nq": m["num_attention_heads"],
            "r": m["kv_lora_rank"], "dn": m["qk_nope_head_dim"],
            "dr": m["qk_rope_head_dim"], "dv": m["v_head_dim"],
            "ff_dense": m["intermediate_size"],
            "ff": m["moe_intermediate_size"],
            "E": m["router_n_experts"], "held": m["n_routed_experts"],
            "first": m["first_held_expert"], "k": m["num_experts_per_tok"],
            "ff_shared": m["n_shared_experts"] * m["moe_intermediate_size"],
            "V": m["vocab_size"], "vocab": m["vocab_size"],
            "theta": float(m["rope_theta"]), "eps": m["rms_norm_eps"],
            "scale": m["routed_scaling_factor"]}


def program_fields(m: dict) -> dict:
    """ModelConfig fields the program must hold to run this config."""
    k = dims(m)
    return {"family": "moe", "n_layers": k["L"], "d_model": k["d"],
            "n_heads": k["nq"], "kv_lora_rank": k["r"],
            "qk_nope_head_dim": k["dn"], "qk_rope_head_dim": k["dr"],
            "v_head_dim": k["dv"], "first_dense_layers": k["Ld"],
            "dense_ff": k["ff_dense"], "d_ff": k["ff"],
            "n_experts": k["E"], "n_experts_held": k["held"],
            "first_expert": k["first"], "top_k": k["k"],
            "shared_expert_ff": k["ff_shared"], "router_score": "sigmoid",
            "routed_scale": k["scale"], "norm": "rmsnorm",
            "norm_eps": k["eps"], "mlp": "swiglu", "rope_theta": k["theta"],
            "vocab": k["vocab"], "padded_vocab": k["V"],
            "tie_embeddings": False}


def _attn_shapes(k, L):
    bf = jnp.bfloat16
    d, nq = k["d"], k["nq"]
    return {"norm1": ((L, d), bf), "norm2": ((L, d), bf),
            "wq": ((L, d, nq * (k["dn"] + k["dr"])), bf),
            "wkv_a": ((L, d, k["r"] + k["dr"]), bf),
            "kv_norm": ((L, k["r"]), bf),
            "wk_b": ((L, k["r"], nq * k["dn"]), bf),
            "wv_b": ((L, k["r"], nq * k["dv"]), bf),
            "wo": ((L, nq * k["dv"], d), bf)}


def _mlp_shapes(d, ff, L, prefix=""):
    bf = jnp.bfloat16
    return {prefix + "w_gate": ((L, d, ff), bf),
            prefix + "w_up": ((L, d, ff), bf),
            prefix + "w_out": ((L, ff, d), bf)}


def param_shapes(m: dict) -> dict:
    k = dims(m)
    d, Ld = k["d"], k["Ld"]
    Lm = k["L"] - Ld
    bf = jnp.bfloat16
    moe = {**_attn_shapes(k, Lm),
           **_mlp_shapes(d, k["ff_shared"], Lm, "shared_"),
           "router": ((Lm, d, k["E"]), bf),
           "router_bias": ((Lm, k["E"]), jnp.float32),
           "we_in": ((Lm, k["held"], d, 2 * k["ff"]), bf),
           "we_out": ((Lm, k["held"], k["ff"], d), bf)}
    return {"emb": ((k["V"], d), bf), "lm_head": ((k["V"], d), bf),
            "final_norm": ((d,), bf),
            "dense_layers": {**_attn_shapes(k, Ld),
                             **_mlp_shapes(d, k["ff_dense"], Ld)},
            "layers": moe}


OUT_PROJ = ("wo", "w_out", "shared_w_out", "we_out")


def init_params(m: dict, seed: int) -> dict:
    """Seeded bf16 weights: norms one; the embedding N(0, 0.02^2);
    matrices N(0, 1/fan_in), the output projections of each block scaled
    down by sqrt(2 (l + 1)), l the layer's index in its stack, as in
    OLMo's init;
    the router's selection bias N(0, 0.1^2), so that it changes which
    experts are chosen and a program that weighed by the biased scores
    would read far off."""
    def init(name, shape, key):
        if name in ("norm1", "norm2", "kv_norm", "final_norm"):
            return jnp.ones(shape)
        if name in ("emb", "lm_head"):
            std = 0.02 if name == "emb" else 1.0 / math.sqrt(shape[-1])
            return jax.random.normal(key, shape) * std
        if name == "router_bias":
            return jax.random.normal(key, shape) * 0.1
        # stacked (L, ..., fan_in, fan_out)
        L = shape[0]
        std = jnp.full((L,), 1.0 / math.sqrt(shape[-2]))
        if name in OUT_PROJ:
            std = std / jnp.sqrt(2.0 * jnp.arange(1, L + 1))
        std = std.reshape((L,) + (1,) * (len(shape) - 1))
        return jax.random.normal(key, shape) * std

    return weights.make(param_shapes(m), init, seed)


def _rms(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (b, s, h, dr) at positions 0..s-1, as DeepSeek-V3's
    ``apply_rotary_pos_emb``: view the last dim as (dr/2, 2), transpose
    to de-interleave, then rotate halves with cos/sin of cat(freqs,
    freqs)."""
    b, s, h, dr = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    freqs = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    emb = jnp.concatenate([freqs, freqs], -1)
    cos, sin = jnp.cos(emb)[None, :, None], jnp.sin(emb)[None, :, None]
    x = x.reshape(b, s, h, dr // 2, 2).swapaxes(-1, -2).reshape(b, s, h, dr)
    rot = jnp.concatenate([-x[..., dr // 2:], x[..., :dr // 2]], -1)
    return x * cos + rot * sin


def dot(x, w):
    """x (..., k) @ w (k, n) in f32 at the highest precision."""
    return jnp.einsum("...k,kn->...n", x, w, precision=HI)


def _swiglu(h, w_gate, w_up, w_out, mm):
    return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_out)


def logits(m: dict, params: dict, tokens: jax.Array, matmul=dot):
    """tokens (b, s) -> logits (b, s, V) f32.  ``matmul`` computes every
    product with a weight matrix, the router's included (a
    lower-precision control swaps it)."""
    k = dims(m)
    mm = matmul
    f32 = jnp.float32
    nq, r, dn, dr, dv, ff = (k["nq"], k["r"], k["dn"], k["dr"], k["dv"],
                             k["ff"])
    x = params["emb"][tokens].astype(f32)
    b, s, _ = x.shape
    causal = jnp.tril(jnp.ones((s, s), bool))

    def attn(x, w):
        h = _rms(x, w["norm1"], k["eps"])
        q = mm(h, w["wq"]).reshape(b, s, nq, dn + dr)
        kv_a = mm(h, w["wkv_a"])
        c = _rms(kv_a[..., :r], w["kv_norm"], KV_NORM_EPS)
        k_pe = _rope(kv_a[..., None, r:], k["theta"])
        k_nope = mm(c, w["wk_b"]).reshape(b, s, nq, dn)
        v = mm(c, w["wv_b"]).reshape(b, s, nq, dv)
        qq = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], k["theta"])],
                             -1)
        kk = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_pe, (b, s, nq, dr))], -1)
        sc = jnp.einsum("bqhd,bkhd->bhqk", qq, kk,
                        precision=HI) / math.sqrt(dn + dr)
        sc = jnp.where(causal, sc, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v,
                       precision=HI).reshape(b, s, nq * dv)
        x = x + mm(o, w["wo"])
        return x, _rms(x, w["norm2"], k["eps"])

    def dense_layer(x, lp):
        w = {n: a.astype(f32) for n, a in lp.items()}
        x, h = attn(x, w)
        return x + _swiglu(h, w["w_gate"], w["w_up"], w["w_out"], mm), None

    def moe_layer(x, lp):
        w = {n: a.astype(f32) for n, a in lp.items()}
        x, h = attn(x, w)
        scores = jax.nn.sigmoid(mm(h, w["router"]))           # (b, s, E)
        _, chosen = jax.lax.top_k(scores + w["router_bias"], k["k"])
        wt = jnp.take_along_axis(scores, chosen, -1)
        wt = wt / wt.sum(-1, keepdims=True) * k["scale"]
        out = _swiglu(h, w["shared_w_gate"], w["shared_w_up"],
                      w["shared_w_out"], mm)
        for e in range(k["held"]):
            gate = jnp.where(chosen == k["first"] + e, wt, 0.0).sum(-1)
            w_in = w["we_in"][e]
            y = _swiglu(h, w_in[:, :ff], w_in[:, ff:], w["we_out"][e], mm)
            out = out + gate[..., None] * y
        return x + out, None

    x, _ = jax.lax.scan(dense_layer, x, params["dense_layers"])
    x, _ = jax.lax.scan(moe_layer, x, params["layers"])
    x = _rms(x, params["final_norm"].astype(f32), k["eps"])
    out = mm(x, params["lm_head"].astype(f32).T)
    return jnp.where(jnp.arange(k["V"]) < k["vocab"], out, -jnp.inf)


def step_weights(m: dict, rows: int) -> dict:
    """Weights one decode step of ``rows`` rows reads (counts, not
    bytes), by part: every held expert's weights are counted as many
    times as routing is expected to touch it (uniform routing: an expert
    is untouched with probability (1 - k/E)^rows)."""
    k = dims(m)
    d, nq, r, dn, dr, dv = k["d"], k["nq"], k["r"], k["dn"], k["dr"], k["dv"]
    Ld, Lm = k["Ld"], k["L"] - k["Ld"]
    mla = (d * nq * (dn + dr) + d * (r + dr) + r * nq * (dn + dv)
           + nq * dv * d)
    expert = 3 * d * k["ff"]
    touched = k["held"] * (1.0 - (1.0 - k["k"] / k["E"]) ** rows)
    return {"mla": k["L"] * mla, "dense_ffn": Ld * 3 * d * k["ff_dense"],
            "shared": Lm * 3 * d * k["ff_shared"], "router": Lm * d * k["E"],
            "experts_read": Lm * touched * expert,
            "experts_per_row": Lm * k["k"] * k["held"] / k["E"] * expert,
            "head": k["V"] * d,
            "norms": k["L"] * (2 * d + r) + d}


def decode_cost(m: dict, positions) -> tuple:
    """(FLOPs, bytes) one decode step needs for live rows at ``positions``
    (the position each live row writes).  Bytes: the weights read once
    (the held experts that the step's routing touches, in expectation;
    the embedding's rows of the step's tokens; the router's bias), each
    row's latent cache rows up to its position (512 + 64 values a layer)
    and its new row.  FLOPs: 2 per weight per row for everything but the
    routed experts, 2 per expert weight per routed token (k * held / E
    of them a row in expectation), and the absorbed attention's two
    products over the rows each attends (score over 576 dims, value over
    512, per head)."""
    k = dims(m)
    n = len(positions)
    w = step_weights(m, n)
    Lm = k["L"] - k["Ld"]
    dense_w = w["mla"] + w["dense_ffn"] + w["shared"] + w["router"] + \
        w["head"]
    weight_bytes = 2 * (dense_w + w["experts_read"] + w["norms"]
                        + n * k["d"]) + 4 * Lm * k["E"]
    row = k["L"] * (k["r"] + k["dr"]) * 2
    lat_bytes = sum(row * (p + 1) for p in positions)
    attn = k["L"] * 2 * k["nq"] * (2 * k["r"] + k["dr"])
    flops = sum(2 * (dense_w + w["experts_per_row"]) + attn * (p + 1)
                for p in positions)
    return float(flops), float(weight_bytes + lat_bytes)
