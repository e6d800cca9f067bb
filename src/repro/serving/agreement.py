"""Decode-vs-forward agreement: does the serve step reproduce the model?

The serve step, teacher-forced over ``length`` positions, picks a greedy
token at each; the model's full forward pass over the same tokens gives
the logits those picks are judged against.  Random bf16 weights leave
near-ties that reduction order on a device can flip, so a pick agrees
when its forward logit is within ``NEAR_TOP`` logit standard deviations
of the best.  A context-free forward (every token alone at position 0)
is the control: a decode that ignores its cache agrees about as little,
so the control must stay at or below ``CONTROL_MAX`` for the check to
tell the two apart.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional

import jax
import numpy as np

from repro.distributed.comm import local_comm
from repro.models.layers import lm_head_logits
from repro.serving.engine import init_cache, make_serve_step

NEAR_TOP = 0.25         # a pick this close to the best logit (in std) agrees
AGREE_MIN = 0.95        # share of decode picks that must agree
CONTROL_MAX = 0.5       # the context-free control must agree at most this


def near_top(logits: np.ndarray, picked: np.ndarray) -> np.ndarray:
    """Whether each picked token's logit lies within ``NEAR_TOP`` logit
    std of the best: logits (..., V), picked (...) -> bool (...)."""
    got = np.take_along_axis(logits, picked[..., None], -1)[..., 0]
    return (logits.max(-1) - got) / logits.std(-1) <= NEAR_TOP


@dataclasses.dataclass
class Agreement:
    top1: float          # exact greedy match with the forward pass
    agree: float         # near-top share of the decode's picks
    control: float       # near-top share of the context-free picks

    def failures(self) -> List[str]:
        out = []
        if self.agree < AGREE_MIN:
            out.append(f"decode disagrees with the forward pass: "
                       f"{self.agree:.4f} < {AGREE_MIN}")
        if self.control > CONTROL_MAX:
            out.append(f"the control agrees {self.control:.4f} > "
                       f"{CONTROL_MAX}: the check cannot tell a decode "
                       f"that ignores its cache from a correct one")
        return out


def decode_agreement(cfg, model, params, *, length: int, batch: int,
                     serve_step: Optional[Callable] = None) -> Agreement:
    """Teacher-force ``serve_step`` (default: the engine's) over
    ``length`` x ``batch`` random tokens and score it against the full
    forward pass."""
    comm = local_comm()

    @jax.jit
    def forward_logits(p, tokens):
        head = p.get("lm_head", p["emb"])
        x, _ = model.forward(p, {"tokens": tokens, "labels": tokens},
                             remat=False)
        return jax.vmap(lambda xp: lm_head_logits(
            xp, head, comm, real_vocab=cfg.vocab)[:, :cfg.vocab])(x)

    tokens = jax.random.randint(jax.random.PRNGKey(1), (length, batch), 0,
                                cfg.vocab)
    logits = np.asarray(forward_logits(params, tokens))     # (L, B, V)
    control = np.asarray(forward_logits(params, tokens.reshape(1, -1))
                         ).reshape(logits.shape).argmax(-1)

    step = jax.jit(serve_step or make_serve_step(cfg), donate_argnums=(1,))
    cache = init_cache(cfg, length, batch)
    preds = []
    for i in range(length):
        nxt, cache = step(params, cache, tokens[i])
        preds.append(np.asarray(nxt))
    preds = np.stack(preds)
    return Agreement(top1=float((preds == logits.argmax(-1)).mean()),
                     agree=float(near_top(logits, preds).mean()),
                     control=float(near_top(logits, control).mean()))
