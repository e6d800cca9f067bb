"""Plain OLMo forward (arXiv:2402.00838) in float32 at the highest matmul
precision, independent of the program: no cache, no batching, no kernels.

Layer: x += Attn(LN(x)); x += SwiGLU(LN(x)).  LN is non-parametric
(eps 1e-5), attention is causal multi-head with rotary embeddings
(rotate-half, theta from the config) and 1/sqrt(head_dim) scaling, the
head is tied to the embedding.  The final norm's weight is applied (the
weights made here set it to one, as OLMo's norm has none).

The weights are made here from the seed, in the layout the program takes
(``param_shapes``), and handed to both the program and this forward.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import weights

LN_EPS = 1e-5
HI = jax.lax.Precision.HIGHEST


def dims(m: dict) -> dict:
    d, nq = m["hidden_size"], m["num_attention_heads"]
    return {"L": m["num_hidden_layers"], "d": d, "nq": nq,
            "nkv": m["num_key_value_heads"], "dh": d // nq,
            "ff": m["intermediate_size"], "V": m["embedding_size"],
            "vocab": m["vocab_size"], "theta": m["rope_theta"]}


def program_fields(m: dict) -> dict:
    """ModelConfig fields the program must hold to run this config."""
    k = dims(m)
    return {"family": "dense", "n_layers": k["L"], "d_model": k["d"],
            "n_heads": k["nq"], "n_kv_heads": k["nkv"], "d_ff": k["ff"],
            "vocab": k["vocab"], "padded_vocab": k["V"], "mlp": "swiglu",
            "norm": "layernorm_np", "tie_embeddings": True,
            "rope_theta": k["theta"], "resolved_head_dim": k["dh"]}


def param_shapes(m: dict) -> dict:
    k = dims(m)
    L, d, ff = k["L"], k["d"], k["ff"]
    bf = jnp.bfloat16
    return {"emb": ((k["V"], d), bf), "final_norm": ((d,), bf),
            "layers": {"wq": ((L, d, k["nq"] * k["dh"]), bf),
                       "wk": ((L, d, k["nkv"] * k["dh"]), bf),
                       "wv": ((L, d, k["nkv"] * k["dh"]), bf),
                       "wo": ((L, k["nq"] * k["dh"], d), bf),
                       "w_gate": ((L, d, ff), bf), "w_up": ((L, d, ff), bf),
                       "w_out": ((L, ff, d), bf)}}


def init_params(m: dict, seed: int) -> dict:
    """Seeded bf16 weights after OLMo's "mitchell" init: input projections
    N(0, 1/fan_in); the output projections of layer l (``wo``, ``w_out``)
    N(0, 1/(2 fan_in (l + 1))); the embedding N(0, 0.02^2), so the tied
    head's logits have a standard deviation near 1 and the residual
    stream, not the input token, decides them."""
    def init(name, shape, key):
        if name == "final_norm":
            return jnp.ones(shape)
        if name == "emb":
            return jax.random.normal(key, shape) * 0.02
        std = 1.0 / jnp.sqrt(jnp.full(shape[:1], float(shape[-2])))
        if name in ("wo", "w_out"):
            std = std / jnp.sqrt(2.0 * jnp.arange(1, shape[0] + 1))
        return jax.random.normal(key, shape) * std[:, None, None]

    return weights.make(param_shapes(m), init, seed)


def _ln(x):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS)


def _rope(x, theta):
    """x (b, s, h, dh): rotate-half rotary embedding at positions 0..s-1."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def dot(x, w):
    """x (..., k) @ w (k, n) in f32 at the highest precision."""
    return jnp.einsum("...k,kn->...n", x, w, precision=HI)


def logits(m: dict, params: dict, tokens: jax.Array, matmul=dot):
    """tokens (b, s) -> logits (b, s, V) f32.  ``matmul`` computes every
    product with a weight matrix (a lower-precision control swaps it)."""
    k = dims(m)
    mm = matmul
    f32 = jnp.float32
    emb = params["emb"].astype(f32)
    x = emb[tokens]
    b, s, _ = x.shape
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lp):
        w = {n: a.astype(f32) for n, a in lp.items()}
        h = _ln(x)
        q, kk, v = mm(h, w["wq"]), mm(h, w["wk"]), mm(h, w["wv"])
        q = _rope(q.reshape(b, s, k["nq"], k["dh"]), k["theta"])
        kk = _rope(kk.reshape(b, s, k["nkv"], k["dh"]), k["theta"])
        v = v.reshape(b, s, k["nkv"], k["dh"])
        g = k["nq"] // k["nkv"]
        kk, v = jnp.repeat(kk, g, axis=2), jnp.repeat(v, g, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, kk,
                        precision=HI) / math.sqrt(k["dh"])
        sc = jnp.where(causal, sc, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v,
                       precision=HI).reshape(b, s, -1)
        x = x + mm(o, w["wo"])
        h = _ln(x)
        x = x + mm(jax.nn.silu(mm(h, w["w_gate"])) * mm(h, w["w_up"]),
                   w["w_out"])
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _ln(x) * params["final_norm"].astype(f32)
    out = mm(x, emb.T)
    return jnp.where(jnp.arange(k["V"]) < k["vocab"], out, -jnp.inf)


def decode_cost(m: dict, positions) -> tuple:
    """(FLOPs, bytes) one decode step needs for live rows at ``positions``
    (the position each live row writes).  Weights are read once; each row
    reads the K/V rows below its position and writes its new row; FLOPs are
    the matmuls (2 per weight per row, the head included) and attention's
    two products over the rows it attends (position + 1)."""
    k = dims(m)
    L, d, dh, nq, nkv, ff, V = (k["L"], k["d"], k["dh"], k["nq"], k["nkv"],
                                k["ff"], k["V"])
    n_mat = L * (d * nq * dh + 2 * d * nkv * dh + nq * dh * d + 3 * d * ff)
    weight_bytes = 2 * (n_mat + V * d + d)         # the final norm too
    row = L * 2 * nkv * dh * 2                  # K and V of one position
    kv_bytes = sum(row * (p + 1) for p in positions)
    flops = sum(2 * (n_mat + V * d) + L * 4 * nq * dh * (p + 1)
                for p in positions)
    return float(flops), float(weight_bytes + kv_bytes)
