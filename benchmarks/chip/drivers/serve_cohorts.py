"""Serving driver: closed cohorts through the program's serving path.

It builds what ``repro.launch.serve.serve`` builds with ``--transport``:
a two-rank ``LocalCluster``, a ``ServeTransport`` (prompts on the
prefill endpoint, results on the decode endpoint), a ``PagedKVAllocator``
sized for the cohort, a ``ServeScheduler`` and the jitted
``make_serve_step``, with that function's decode adapter.  The only
thing added is a fresh cache per cohort (the adapter decodes every row at
one position front, so a cohort starts from position 0); both sit inside
the window.  A cohort's next cohort is submitted only when every result
of the current one has been delivered.

The window runs whole cycles of the mix: it closes at the first cycle
boundary at or after ``seconds``, so every run serves the same set of
output lengths.
"""
from __future__ import annotations

import math
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.runtime import LocalCluster
from repro.serving import PagedKVAllocator, ServeScheduler, ServeTransport
from repro.serving.engine import init_cache, make_serve_step

from benchmarks.chip import traffic

PAGE = 16


def _posts(tport) -> int:
    c = tport.counters()
    return sum(d["posts"] for side in ("prefill", "decode")
               for ep in c[side] for d in ep["devices"])


def run(ctx) -> Dict:
    cfg, mix, spans = ctx.program_cfg, ctx.mix, ctx.spans
    n, cache_len = mix["cohort_size"], mix["cache_len"]
    params = ctx.params
    # a request writes its prompt and all but its last token to the cache
    longest = mix["prompt_len"] + mix["lengths"]["max"]
    if longest - 1 > cache_len:
        raise ValueError(f"cache_len {cache_len} < longest request "
                         f"{longest - 1}")

    # -- set-up: what serve() builds, sized for the cohort --------------
    cache = jax.eval_shape(lambda: init_cache(cfg, cache_len, n))
    step_fn = jax.jit(make_serve_step(cfg), donate_argnums=(1,)).lower(
        params, cache, jnp.zeros((n,), jnp.int32)).compile()
    state = {"cache": None, "pos": 0}
    rounds: List[tuple] = []        # (positions written, in trace) per step

    def decode_fn(tokens, positions):
        # serve()'s adapter: the whole active batch at one position front
        if state["pos"] >= cache_len:
            raise RuntimeError(f"decode ran past cache_len={cache_len}")
        toks = jnp.asarray(np.pad(tokens, (0, n - len(tokens))), jnp.int32)
        with spans("bench.serve_step"):
            nxt, state["cache"] = step_fn(params, state["cache"], toks)
            out = np.asarray(nxt)[:len(tokens)]
        rounds.append(([state["pos"]] * len(tokens), ctx.tracing))
        state["pos"] += 1
        return out

    cluster = LocalCluster(2)
    tport = ServeTransport(cluster, n_prefill=2)
    sched = ServeScheduler(decode_fn, max_batch=n, transport=tport,
                           allocator=PagedKVAllocator(
                               n_pages=n * math.ceil(longest / PAGE),
                               page_size=PAGE))
    sent: Dict[int, tuple] = {}     # rid -> (t_submit, prompt, max_new,
    #                                        counted in the window)
    got: Dict[int, list] = {}       # rid -> [(t_arrival, tokens), ...]

    def cohort(prompts, max_new, record=True):
        with spans("bench.cohort_start"):
            state["cache"] = None           # free the last cohort's first
            state["cache"] = init_cache(cfg, cache_len, n)
            state["pos"] = 0
        pending = set()
        with spans("bench.submit"):
            for prompt, m in zip(prompts, max_new):
                rid = sched.submit_remote(prompt, m)
                sent[rid] = (time.perf_counter(), prompt, m, record)
                pending.add(rid)
        steps = 0
        while pending:
            with spans("bench.round"):
                with spans("bench.sched_step"):
                    sched.step()
                with spans("bench.transport_pump"):
                    tport.pump()
                with spans("bench.poll"):
                    res = tport.poll_results()
            now = time.perf_counter()
            for rid, toks in res:
                got.setdefault(rid, []).append((now, toks))
                pending.discard(rid)
            steps += 1
            ctx.after_round()
            if steps > max(max_new) + 8:
                return False        # results that never came: undelivered
        return True

    # warm-up: one cohort of two tokens each through the whole path
    gen = traffic.cohorts(mix, ctx.seed, ctx.vocab)
    first = next(gen)
    cohort(first.prompts, [min(2, m) for m in first.max_new], record=False)
    rounds.clear()

    # -- the window ---------------------------------------------------------
    posts0 = _posts(tport)
    t_start = ctx.window_start()
    cyc, c = None, first
    while True:
        if c.cycle != cyc:
            if cyc is not None and time.perf_counter() - t_start >= \
                    ctx.seconds:
                break
            cyc = c.cycle
        if not cohort(c.prompts, c.max_new):
            break
        c = next(gen)
    t_end = time.perf_counter()
    ctx.window_end()
    posts = _posts(tport) - posts0
    cluster.close()

    # -- what the client saw ----------------------------------------------
    mine = {rid: v for rid, v in sent.items() if v[3]}
    lat, tokens, failed = [], 0, 0
    dup = sum(1 for rid in mine if len(got.get(rid, [])) > 1)
    undelivered = sum(1 for rid in mine if rid not in got)
    wrong_len = 0
    served = {}
    for rid, (t_sub, prompt, m, _) in mine.items():
        if rid not in got:
            failed += 1
            continue
        t_arr, toks = got[rid][0]
        if len(toks) != m:
            wrong_len += 1
            failed += 1
        lat.append(t_arr - t_sub)
        tokens += len(toks)
        served[rid] = (prompt, toks)
    lat.sort()
    window = t_end - t_start
    del state, step_fn, cache, sched, tport, cluster
    return {
        "attempted": len(mine), "failed": failed,
        "e2e": {"decode_tokens_per_s": tokens / window,
                "request_p95_ms": 1e3 * lat[max(0, math.ceil(0.95 * len(lat))
                                               - 1)]},
        "checks": [("undelivered", undelivered, 0),
                   ("delivered_twice", dup, 0),
                   ("wrong_token_count", wrong_len, 0)],
        "served": served,
        "layer": {"rounds": len(rounds),
                  "host_s": (spans.total("bench.round", t_start, t_end)[0]
                             - spans.total("bench.serve_step", t_start,
                                           t_end)[0]),
                  "wire_posts": posts, "completed": len(served),
                  "traced_positions": [p for p, tr in rounds if tr]},
    }
