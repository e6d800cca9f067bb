"""Serving driver: ``serve_cohorts`` unchanged, then, in traced runs, the
program's own scopes and its expert-load counter.

The window is ``serve_cohorts.run``'s.  Before anything compiles, op
metadata is put into the compile cache's key, so a step loaded from the
cache maps its ops to this program's scopes.  In a traced run, after the
window and before the trace is removed:

* the same step is lowered and compiled once more, and its optimized HLO
  gives each op's scope (``program_trace.op_scopes``);
* ``program_trace.program_layers`` reads, per step, the device time of
  the ops under each of the serve step's scopes (``SCOPES``);
* one cohort of the run's own traffic (its first, ``trace_rounds`` steps)
  is decoded again from a fresh cache, and the step's expert-load counter
  (``DecodeCache.expert_load``, accumulated on the device) is read once at
  its end: the window's caches are freed cohort by cohort inside
  ``serve_cohorts``, so the counter is read from this replay.

All of it goes into ``layer`` (``scope_s``, ``expert_load``).  A held
token that was routed and not combined is a failed check
(``held_tokens_dropped``, limit 0).

In every run, beside ``compare.py``'s widest gap: the share of the
sample's served tokens whose logit lies more than the mix's
``check.gap_over`` below the reference's best (``logit_gap_share``,
limit ``check.gap_share``), over the same sample ``run.py`` draws.
Routing near-ties flip an expert under bf16 on a few tokens, and one
flip can read as wide as a lower-precision control's widest gap; a
lower precision moves many tokens.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

import glob
import json
import os

from benchmarks.chip import compare, program_trace, trace_reduce, traffic
from benchmarks.chip.drivers import serve_cohorts

#: the serve step's scopes read per step (names as the program sets them)
SCOPES = ("mla_attn", "moe_route", "moe_experts", "cache_io")


def _compile_step(cfg, params, mix):
    from repro.serving.engine import init_cache, make_serve_step
    n = mix["cohort_size"]
    cache = jax.eval_shape(lambda: init_cache(cfg, mix["cache_len"], n))
    return jax.jit(make_serve_step(cfg), donate_argnums=(1,)).lower(
        params, cache, jnp.zeros((n,), jnp.int32)).compile()


def scope_times(trace_dir: str, hlo: str) -> Dict[str, float]:
    """Device seconds per step under each scope of ``SCOPES`` (a scope no
    op of the trace maps to is left out)."""
    pd = trace_reduce.load(trace_dir)
    scopes = program_trace.op_scopes(hlo)
    out = {}
    for name in SCOPES:
        r = program_trace.program_layers(
            pd, scopes, scope=name, gap_span="bench.serve_step",
            stretch_span="bench.round", module_match="serve_step")
        if r:
            out[name] = r["scoped_s"]
    return out


def expert_load(ctx, step) -> Dict:
    """The counter after one replayed cohort: tokens routed to each held
    expert of each MoE layer per step (mean and most in one step), and
    the routed tokens the step did not combine."""
    from repro.serving.engine import init_cache
    cfg, mix = ctx.program_cfg, ctx.mix
    first = next(traffic.cohorts(mix, ctx.seed, ctx.vocab))
    cache = init_cache(cfg, mix["cache_len"], mix["cohort_size"])
    if cache.expert_load is None:
        return {}
    toks = jnp.asarray(first.prompts[:, -1], jnp.int32)
    for _ in range(min(mix["trace_rounds"], mix["cache_len"])):
        toks, cache = step(ctx.params, cache, toks)
    load = np.asarray(cache.expert_load)
    steps = int(cache.length)
    del cache
    routed_sum, routed_max, combined = load.astype(np.int64)
    mean = routed_sum.mean() / steps
    return {"steps": steps, "mean_per_step": float(mean),
            "max_per_step": int(routed_max.max()),
            "max_over_mean": float(routed_max.max() / mean) if mean else None,
            "routed": int(routed_sum.sum()),
            "dropped": int(routed_sum.sum() - combined.sum())}


def reference(cfg):
    """(reference module, model dims) of the configuration file whose
    program is ``cfg``."""
    import importlib
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for path in sorted(glob.glob(os.path.join(here, "configs", "*.json"))):
        with open(path) as f:
            conf = json.load(f)
        if conf.get("program") == cfg.name:
            return (importlib.import_module("benchmarks.chip.reference."
                                            + conf["reference"]),
                    conf["model"])
    raise KeyError(f"no configuration file runs program {cfg.name!r}")


def gap_share(ctx, served) -> float:
    """Share of the sample's served tokens whose gap exceeds
    ``check.gap_over``."""
    ref, dims = reference(ctx.program_cfg)
    check, width = ctx.mix["check"], ctx.mix["cache_len"]
    rids = compare.sample(served, ctx.seed, check["requests"],
                          check["longest"])
    toks, tgt, mask = compare.teacher_batch(served, rids, width)
    gaps = jax.jit(lambda p, a, b, c: compare._gaps(ref, dims, p, a, b, c,
                                                    None))
    g = np.asarray(gaps(ctx.params, toks, tgt, mask))[mask]
    return float((g > check["gap_over"]).mean())


def run(ctx) -> Dict:
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    res = serve_cohorts.run(ctx)
    res["checks"].append(("logit_gap_share", gap_share(ctx, res["served"]),
                          ctx.mix["check"]["gap_share"]))
    if not ctx.trace:
        return res
    step = _compile_step(ctx.program_cfg, ctx.params, ctx.mix)
    layer = res["layer"]
    layer["scope_s"] = scope_times(ctx.trace_dir, step.as_text())
    layer["expert_load"] = load = expert_load(ctx, step)
    del step
    if load:
        res["checks"].append(("held_tokens_dropped", load["dropped"], 0))
    return res
