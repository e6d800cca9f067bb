"""The one traffic generator: closed cohorts, read from a mix's JSON file.

A mix file names its driver and gives the cohort size, the prompt length
and the output-length distribution.  ``lengths.per`` is ``"request"``
(every request draws its own length) or ``"cohort"`` (every request of a
cohort shares one length; a cycle of ``lengths.cycle`` cohorts takes the
cycle's evenly spaced quantiles of the distribution, in an order drawn
from the seed, so every seed serves the same set of lengths).
"""
from __future__ import annotations

import json
import statistics
from typing import Iterator, List, NamedTuple

import numpy as np


class Cohort(NamedTuple):
    cycle: int
    prompts: np.ndarray        # (n, prompt_len) int32
    max_new: List[int]         # per request


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2**63, *stream])


def _clip(x: float, spec: dict) -> int:
    return int(min(max(round(x), spec["min"]), spec["max"]))


def quantile_lengths(spec: dict, k: int) -> List[int]:
    """``k`` evenly spaced quantiles of the lognormal, clipped."""
    nd = statistics.NormalDist()
    return [_clip(spec["median"] * np.exp(spec["sigma"]
                                          * nd.inv_cdf((i + 0.5) / k)), spec)
            for i in range(k)]


def cohorts(mix: dict, seed: int, vocab: int) -> Iterator[Cohort]:
    """Endless cohorts for one seed; the same seed gives the same ones."""
    spec = mix["lengths"]
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    n, plen = mix["cohort_size"], mix["prompt_len"]
    per_cohort = spec["per"] == "cohort"
    k = spec["cycle"] if per_cohort else 1
    base = quantile_lengths(spec, k) if per_cohort else None
    cycle = 0
    while True:
        rng = _rng(seed, cycle)
        order = rng.permutation(k)
        for j in range(k):
            prompts = rng.integers(0, vocab, size=(n, plen), dtype=np.int32)
            if per_cohort:
                lens = [base[order[j]]] * n
            else:
                draw = spec["median"] * np.exp(spec["sigma"]
                                               * rng.standard_normal(n))
                lens = [_clip(x, spec) for x in draw]
            yield Cohort(cycle, prompts, lens)
        cycle += 1
