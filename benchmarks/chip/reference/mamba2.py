"""Plain Mamba-2 (arXiv:2405.21060) as a sequential float32 recurrence,
independent of the program: one token at a time, no chunked scan, no
cache layout, no kernels; matmuls at the highest precision.

Per layer, for the token's hidden x (d):

    h = RMSNorm(x) * norm1                          (eps 1e-6)
    z, u, dt_raw, (B, C) = h W_z, h W_x, h W_dt, h W_bc
    u = silu(causal depthwise conv over the last K inputs u)
    dt = softplus(dt_raw + dt_bias),  A = -exp(a_log)
    S_h = exp(dt_h A_h) S_h + dt_h B ⊗ u_h          (per head h, f32 state)
    y_h = C · S_h + D_h u_h
    y = RMSNorm(y * silu(z)) * norm_w               (eps 1e-6, over d_inner)
    x = x + y W_out

then RMSNorm(x) * final_norm and the tied head.  Departures from the
published layer, which the program makes and this reference follows: the
causal conv runs over u only (the paper's block convolves x, B and C
together), there is no conv bias, and both norms use eps 1e-6.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import weights

EPS = 1e-6
HI = jax.lax.Precision.HIGHEST


def dims(m: dict) -> dict:
    d, e, p = m["hidden_size"], m["expand"], m["head_dim"]
    return {"L": m["num_hidden_layers"], "d": d, "di": e * d,
            "h": e * d // p, "p": p, "n": m["state_size"],
            "g": m["n_groups"], "K": m["conv_kernel"],
            "V": m["embedding_size"], "vocab": m["vocab_size"]}


def program_fields(m: dict) -> dict:
    k = dims(m)
    return {"family": "ssm", "n_layers": k["L"], "d_model": k["d"],
            "ssm_d_inner": k["di"], "ssm_heads": k["h"],
            "ssm_headdim": k["p"], "ssm_state": k["n"],
            "ssm_groups": k["g"], "ssm_conv_kernel": k["K"],
            "vocab": k["vocab"], "padded_vocab": k["V"], "norm": "rmsnorm",
            "tie_embeddings": True}


def param_shapes(m: dict) -> dict:
    k = dims(m)
    L, d, di, h, n, g = k["L"], k["d"], k["di"], k["h"], k["n"], k["g"]
    bf, f32 = jnp.bfloat16, jnp.float32
    return {"emb": ((k["V"], d), bf), "final_norm": ((d,), bf),
            "layers": {"norm1": ((L, d), bf),
                       "ssm_w_z": ((L, d, di), bf),
                       "ssm_w_x": ((L, d, di), bf),
                       "ssm_w_dt": ((L, d, h), bf),
                       "ssm_w_bc": ((L, d, 2 * g * n), bf),
                       "ssm_conv_w": ((L, k["K"], di), bf),
                       "ssm_a_log": ((L, h), f32),
                       "ssm_d_skip": ((L, h), f32),
                       "ssm_dt_bias": ((L, h), f32),
                       "ssm_norm_w": ((L, di), bf),
                       "ssm_w_out": ((L, di, d), bf)}}


def init_params(m: dict, seed: int) -> dict:
    """Seeded weights after Mamba-2's published init: A in [1, 16], dt in
    [1e-3, 1e-1] log-uniform through the inverse softplus, D = 1, conv
    N(0, 1/K); input projections N(0, 1/fan_in); the output projection
    N(0, 1/fan_in) over the number of layers (the reference code's
    rescaling of residual projections); embedding N(0, 0.02^2)."""
    n_layers = m["num_hidden_layers"]

    def init(name, shape, key):
        if name in ("final_norm", "norm1", "ssm_norm_w", "ssm_d_skip"):
            return jnp.ones(shape)
        if name == "ssm_a_log":
            return jnp.log(jax.random.uniform(key, shape, minval=1.0,
                                              maxval=16.0))
        if name == "ssm_dt_bias":
            dt = jnp.exp(jax.random.uniform(key, shape, minval=math.log(1e-3),
                                            maxval=math.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        std = 0.02 if name == "emb" else 1.0 / math.sqrt(shape[-2])
        if name == "ssm_w_out":
            std /= math.sqrt(n_layers)
        return jax.random.normal(key, shape) * std

    return weights.make(param_shapes(m), init, seed)


def _rms(x, w):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + EPS) * w


def dot(x, w):
    """x (..., k) @ w (k, n) in f32 at the highest precision."""
    return jnp.einsum("...k,kn->...n", x, w, precision=HI)


def logits(m: dict, params: dict, tokens: jax.Array, matmul=dot):
    """tokens (b, s) -> logits (b, s, V) f32, one token after another.
    ``matmul`` computes every product with a weight matrix (a
    lower-precision control swaps it)."""
    k = dims(m)
    mm = matmul
    f32 = jnp.float32
    b = tokens.shape[0]
    emb = params["emb"].astype(f32)
    lw = {n: a.astype(f32) for n, a in params["layers"].items()}
    state0 = jnp.zeros((k["L"], b, k["h"], k["n"], k["p"]), f32)
    conv0 = jnp.zeros((k["L"], k["K"] - 1, b, k["di"]), f32)

    def layer(x, sl):
        w, S, tail = sl
        hid = _rms(x, w["norm1"])
        z = mm(hid, w["ssm_w_z"])
        u = mm(hid, w["ssm_w_x"])
        dt = jax.nn.softplus(mm(hid, w["ssm_w_dt"])
                             + w["ssm_dt_bias"])                  # (b, h)
        bc = mm(hid, w["ssm_w_bc"])
        B, C = bc[:, :k["n"]], bc[:, k["n"]:]                    # g == 1
        win = jnp.concatenate([tail, u[None]], 0)                # (K, b, di)
        u = jax.nn.silu((win * w["ssm_conv_w"][:, None, :]).sum(0))
        uh = u.reshape(b, k["h"], k["p"])
        A = -jnp.exp(w["ssm_a_log"])
        S = (jnp.exp(dt * A)[..., None, None] * S
             + dt[..., None, None] * B[:, None, :, None] * uh[:, :, None, :])
        y = jnp.einsum("bn,bhnp->bhp", C, S, precision=HI)
        y = (y + w["ssm_d_skip"][:, None] * uh).reshape(b, k["di"])
        y = _rms(y * jax.nn.silu(z), w["ssm_norm_w"])
        return x + mm(y, w["ssm_w_out"]), (S, win[1:])

    def token(carry, tok):
        S, tail = carry
        x, (S, tail) = jax.lax.scan(layer, emb[tok], (lw, S, tail))
        x = _rms(x, params["final_norm"].astype(f32))
        lg = mm(x, emb.T)
        return (S, tail), jnp.where(jnp.arange(k["V"]) < k["vocab"], lg,
                                    -jnp.inf)

    _, out = jax.lax.scan(token, (state0, conv0), tokens.T)
    return out.transpose(1, 0, 2)


def decode_cost(m: dict, positions) -> tuple:
    """(FLOPs, bytes) one decode step needs for its live rows: the weights
    once at their stored dtype, each row's SSM state (f32) and conv tail
    (bf16) read and written; FLOPs are the matmuls (2 per weight per row,
    the head included) and the state update and read-out (about 5 per
    state element: decay, outer product, add, and C's product)."""
    k = dims(m)
    L, d, di, h, n, p, K, V = (k["L"], k["d"], k["di"], k["h"], k["n"],
                               k["p"], k["K"], k["V"])
    n_mat = L * (d * (2 * di + h + 2 * k["g"] * n) + di * d)
    small = L * (d + K * di + di) * 2 + L * 3 * h * 4 + d * 2
    weight_bytes = 2 * (n_mat + V * d) + small
    rows = len(positions)
    state_bytes = rows * L * (2 * h * n * p * 4 + 2 * (K - 1) * di * 2)
    flops = rows * (2 * (n_mat + V * d) + L * 5 * h * n * p)
    return float(flops), float(weight_bytes + state_bytes)
