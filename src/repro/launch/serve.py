"""Serving launcher: continuous batching with the LCI scheduler.

    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --smoke \
        --requests 16 --max-new 12

``--transport`` routes requests over the host runtime's endpoints:
prompts ride a by-size-striped prefill endpoint, generated tokens a
separate decode endpoint (size-class isolation, DESIGN.md §8).

:func:`build` and :func:`serve` are the callable form of the CLI
(``chip_smoke.py`` drives the same path on the chip).
"""
import argparse
import dataclasses
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_NAMES, get_config, get_smoke
from repro.core.runtime import LocalCluster
from repro.launch.compile_cache import enable_compile_cache
from repro.models.common import ModelConfig
from repro.models.registry import Model, build_model
from repro.serving import PagedKVAllocator, ServeScheduler, ServeTransport
from repro.serving.engine import init_cache, make_serve_step


@dataclasses.dataclass
class ServeResult:
    submitted: List[int]                       # request ids, in order
    received: List[Tuple[int, np.ndarray]]     # (rid, tokens) as delivered
    prefill_posts: Optional[List[int]]         # per prefill device
    engine_rounds: int
    retries: int
    compile_s: float                           # serve-step AOT compile
    wall_s: float                              # submit .. last delivery

    @property
    def n_tokens(self) -> int:
        return sum(len(t) for _, t in self.received)


def build(arch: str, *, smoke: bool = False
          ) -> Tuple[ModelConfig, Model, Dict]:
    """Config, model and randomly initialised params (seed 0)."""
    cfg = get_smoke(arch) if smoke else get_config(arch)
    if cfg.family in ("vlm",) or cfg.is_encdec:
        raise ValueError("the serve launcher targets decoder-only archs")
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def serve(cfg: ModelConfig, params, *, requests: int = 16,
          max_new: int = 12, max_batch: int = 8, cache_len: int = 128,
          transport: bool = False, prefill_devices: int = 2,
          drain_workers: int = 0, attrs: Sequence[str] = ()
          ) -> ServeResult:
    """Serve ``requests`` random 8-token prompts, ``max_new`` tokens
    each, through the continuous-batching scheduler."""
    if attrs and not transport:
        raise ValueError("--attr tunes the transport cluster; it needs "
                         "--transport (without it there is no host "
                         "runtime to configure)")
    if drain_workers > 0 and transport:
        raise ValueError("--drain-workers drains the local result CQ; "
                         "with --transport results arrive via "
                         "transport.poll_results() instead — pick one")
    cache = init_cache(cfg, cache_len, max_batch)
    toks0 = jnp.zeros((max_batch,), jnp.int32)
    t0 = time.perf_counter()
    step_fn = jax.jit(make_serve_step(cfg), donate_argnums=(1,)).lower(
        params, cache, toks0).compile()
    compile_s = time.perf_counter() - t0
    state = {"cache": cache, "pos": 0}

    def decode_fn(tokens, positions):
        # the engine decodes the whole active batch at the scheduler's
        # current position front (the cache length is the batch max; the
        # per-request positions drive masking through valid_len)
        if state["pos"] >= cache_len:
            raise RuntimeError(f"decode ran past cache_len={cache_len}")
        pad = max_batch - len(tokens)
        toks = jnp.asarray(np.pad(tokens, (0, pad)), jnp.int32)
        nxt, state["cache"] = step_fn(params, state["cache"], toks)
        state["pos"] += 1
        return np.asarray(nxt)[:len(tokens)]

    alloc = PagedKVAllocator(n_pages=256, page_size=16)
    tport = None
    if transport:
        from repro.core.attrs import parse_attr_args
        cluster = LocalCluster(2, attrs=parse_attr_args(list(attrs)))
        tport = ServeTransport(cluster, n_prefill=prefill_devices)
        echo = cluster.attrs_echo()
        overridden = {k: v for k, v in echo["values"].items()
                      if echo["sources"].get(k) not in (None, "default",
                                                        "discovered")}
        if overridden:
            print(f"[serve] transport attrs (non-default): {overridden}")
    sched = ServeScheduler(decode_fn, max_batch=max_batch,
                           allocator=alloc, transport=tport)
    # unified comp API (routes via transport when present); worker-thread
    # draining needs the thread-safe LCQ backend
    cq = sched.alloc_cq(threadsafe=drain_workers > 0)
    drain = (sched.start_result_drain(cq, drain_workers)
             if drain_workers > 0 else None)
    rng = np.random.default_rng(0)
    received: List[Tuple[int, np.ndarray]] = []
    submitted: List[int] = []
    t0 = time.perf_counter()
    for _ in range(requests):
        prompt = rng.integers(0, cfg.vocab, size=8)
        if tport is not None:
            submitted.append(sched.submit_remote(prompt, max_new))
        else:
            st = sched.submit(prompt, max_new, comp=cq, allow_retry=False)
            assert not st.is_retry()
            submitted.append(st.user_context)
    steps = 0
    while sched.completed < requests:
        sched.step()
        if tport is not None:
            tport.pump()
            received += tport.poll_results()
        steps += 1
        if steps > requests * max_new * 4:
            raise RuntimeError("scheduler stalled")
    prefill_posts = None
    if tport is not None:
        tport.pump()
        received += tport.poll_results()
        prefill_posts = [d["posts"] for d in
                         tport.counters()["prefill"][0]["devices"]]
    from repro.core.concurrency import drain as drain_cq
    statuses = drain.stop() if drain is not None else []
    statuses += drain_cq(cq)
    received += [(st.tag, np.asarray(st.get_buffer())) for st in statuses]
    return ServeResult(submitted=submitted, received=received,
                       prefill_posts=prefill_posts, engine_rounds=steps,
                       retries=sched.retries, compile_s=compile_s,
                       wall_s=time.perf_counter() - t0)


def main():
    # before any backend starts: jax fixes the device count on first use
    if os.environ.get("REPRO_DEVICES"):
        os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                                   + os.environ["REPRO_DEVICES"])
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_NAMES)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--transport", action="store_true",
                    help="route requests over prefill/decode endpoints")
    ap.add_argument("--prefill-devices", type=int, default=2)
    ap.add_argument("--drain-workers", type=int, default=0,
                    help="drain the result CQ from N worker threads "
                         "(thread-safe LCQ-backed queue, DESIGN.md §10)")
    ap.add_argument("--attr", action="append", default=[],
                    metavar="NAME=VALUE",
                    help="runtime-level attribute override for the "
                         "transport cluster (repeatable; e.g. "
                         "--attr rdv_threshold=4096 — DESIGN.md §12)")
    args = ap.parse_args()

    enable_compile_cache()
    try:
        cfg, _, params = build(args.arch, smoke=args.smoke)
        res = serve(cfg, params, requests=args.requests,
                    max_new=args.max_new, max_batch=args.max_batch,
                    cache_len=args.cache_len, transport=args.transport,
                    prefill_devices=args.prefill_devices,
                    drain_workers=args.drain_workers, attrs=args.attr)
    except ValueError as e:
        raise SystemExit(str(e))
    if res.prefill_posts is not None:
        print(f"[serve] prefill endpoint posts per device: "
              f"{res.prefill_posts}")
    if args.drain_workers > 0:
        print(f"[serve] {args.drain_workers} drain workers collected "
              f"{len(res.received)} results concurrently")
    print(f"[serve] {args.requests} requests, {res.n_tokens} tokens in "
          f"{res.wall_s:.2f}s ({res.n_tokens / res.wall_s:.1f} tok/s, "
          f"{res.engine_rounds} engine rounds, {res.retries} admission "
          f"retries; compile {res.compile_s:.2f}s)")


if __name__ == "__main__":
    main()
