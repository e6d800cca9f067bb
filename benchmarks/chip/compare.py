"""The comparison that decides ``correct`` for a served model.

Once the window has closed, a sample of the finished requests, drawn from
the seed and holding the longest ones, is run through the plain reference
(teacher-forced over its prompt and its served tokens).  For every served
token the gap by which the reference's logit for it lies below the
reference's best logit at that position is read; the widest gap is the
number compared.  Greedy decoding that computed the stated model reads a
gap near rounding; a token decoded from the wrong context reads the
spread of the logits.

The control puts the reference in the program's place at a lower
precision: ``int8`` computes every product with a weight matrix in int8
(the weight rounded with one scale per output column, the activation with
one scale per row, as int8 serving does); ``fp8`` rounds both operands
to float8 e4m3 the same way.  At each position a control reads the gap of
the token it ranks first.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def sample(served: Dict[int, tuple], seed: int, n: int, longest: int):
    """Request ids: the ``longest`` with the most served tokens, then the
    rest drawn from the seed."""
    rids = sorted(served, key=lambda r: (-len(served[r][1]), r))
    pick = rids[:longest]
    rest = rids[longest:]
    rng = np.random.default_rng([seed % 2**63, 7])
    k = min(n - len(pick), len(rest))
    pick += [rest[i] for i in sorted(rng.choice(len(rest), k,
                                                replace=False))]
    return pick


def teacher_batch(served, rids, width: int):
    """(tokens, targets, mask): row i feeds [prompt, served[:-1]] and
    expects served at each position."""
    b = len(rids)
    toks = np.zeros((b, width), np.int32)
    tgt = np.zeros((b, width), np.int32)
    mask = np.zeros((b, width), bool)
    for i, rid in enumerate(rids):
        prompt, out = served[rid]
        seq = np.concatenate([prompt, out]).astype(np.int32)
        p = len(prompt)
        toks[i, :len(seq) - 1] = seq[:-1]
        tgt[i, p - 1:len(seq) - 1] = out
        mask[i, p - 1:len(seq) - 1] = True
    return toks, tgt, mask


def _int8(a: jax.Array, axis: int) -> jax.Array:
    """``a`` rounded to int8 with one scale along ``axis``."""
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(a / scale).clip(-127, 127) * scale


def int8_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.einsum("...k,kn->...n", _int8(x, -1), _int8(w, 0),
                      precision=jax.lax.Precision.HIGHEST)


def fp8_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """Both operands rounded to float8 e4m3, each scaled into its range
    (per row of the activation, per column of the weight)."""
    def q(a, axis):
        scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 448.0
        scale = jnp.where(scale == 0, 1.0, scale)
        return (a / scale).astype(jnp.float8_e4m3fn).astype(a.dtype) * scale
    return jnp.einsum("...k,kn->...n", q(x, -1), q(w, 0),
                      precision=jax.lax.Precision.HIGHEST)


CONTROLS = {"int8": int8_matmul, "fp8": fp8_matmul}


def _gaps(ref, dims, params, toks, tgt, mask, control):
    with jax.default_matmul_precision("highest"):
        lg = ref.logits(dims, params, toks)
        best = lg.max(-1)
        if control is None:
            pick = tgt
        else:
            pick = jnp.argmax(ref.logits(dims, params, toks,
                                         matmul=CONTROLS[control]), -1)
        val = jnp.take_along_axis(lg, pick[..., None], -1)[..., 0]
        return jnp.where(mask, best - val, 0.0)


def widest_gap(ref, dims, params, served, rids, width,
               control=None) -> Tuple[float, int]:
    toks, tgt, mask = teacher_batch(served, rids, width)
    fn = jax.jit(lambda p, a, b, c: _gaps(ref, dims, p, a, b, c, control))
    g = np.asarray(fn(params, toks, tgt, mask))
    return float(g.max()), int(mask.sum())
