"""Stage-scoped timer spans (DESIGN.md §15).

``with tele.span("post_burst"): ...`` times one stage and records the
duration into the metric registry's log2 histogram for that stage
(key ``span:<stage>``).  At trace level every span also enters and
exits a ``jax.profiler.TraceAnnotation`` named after its stage, so a
running profiler session records the stage in its host plane, nested
as the spans nest and on the same clock as the device's operations;
with no session running the annotation is close to free.

The off-level fast path is the whole design: :meth:`Telemetry.span`
returns the module-level :data:`NULL_SPAN` singleton when timers are
disabled — no allocation, no clock read, nothing but one attribute
branch at the call site.

The stage taxonomy is :data:`STAGES`: every stage the program opens a
span with is one of its keys.
"""
from __future__ import annotations

import time
from typing import Dict

from .counters import quantile_bound

#: histogram key prefix for stage spans
SPAN_PREFIX = "span:"

#: every instrumented stage, with what one span of it covers
STAGES = {
    "post": "one scalar ProgressEngine.post",
    "post_burst": "one post_burst doorbell (fused or scalar runs)",
    "progress": "one full progress pass (outer span)",
    "progress.backlog": "backlog redelivery sub-stage",
    "progress.tx_sweep": "source-completion sweep sub-stage",
    "progress.drain": "fabric drain + reaction-chain sub-stage",
    "progress.rel": "reliability sweep: retransmits, deadlines, acks",
    "transport.push": "one fabric try_push/push_burst/push_packed",
    "transport.drain": "one fabric drain call (any backend)",
    "pool.get": "packet pool get/get_n (lane lock + steal)",
    "pool.put": "packet pool put/put_n",
    "match.now": "lock-free pre-posted-recv probe",
    "match.insert": "bucket-locked matching insert",
    "cq.pop": "one completion-queue pop",
    "signal": "one batched completion delivery (signal_many)",
    "worker.sweep": "one worker pass over its (engine, device) targets",
    "worker.nap": "one idle-backoff sleep in the worker loop",
    "serve.enqueue": "ContinuousBatcher: one request into the queue",
    "serve.prefill": "ContinuousBatcher: one prefill of a new request",
    "serve.insert": "ContinuousBatcher: one prefilled row into the batch",
    "serve.decode": "ContinuousBatcher: one decode tick",
    "serve.deliver": "ContinuousBatcher: one tick's result delivery",
    "serve.drain": "ResultDrain: one popped result",
    "sched.step": "one ServeScheduler.step round",
    "sched.decode": "a round's decode_fn call, until its tokens are on "
                    "the host",
}


class _NullSpan:
    """The compiled-away span: a no-op context manager singleton."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class Span:
    """One live stage measurement (constructed only when timers are on),
    inside the profiler annotation of its stage at trace level."""

    __slots__ = ("_tele", "stage", "_t0", "_ann")

    def __init__(self, tele, stage: str):
        self._tele = tele
        self.stage = stage
        self._ann = (tele.annotation(stage) if tele.annotation is not None
                     else None)

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter_ns() - self._t0
        self._tele.registry.observe(SPAN_PREFIX + self.stage, dur)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


def summarize_spans(spans: Dict[str, Dict]) -> Dict[str, Dict]:
    """Render raw span histograms (``{stage: {count, sum, buckets}}``)
    into the BENCH-JSON summary: count, total time, and p50/p99 bucket
    estimates in microseconds; the sparse buckets ride along so merged
    documents stay re-mergeable."""
    out: Dict[str, Dict] = {}
    for stage, h in sorted(spans.items()):
        buckets = h.get("buckets", {})
        out[stage] = {
            "count": h.get("count", 0),
            "total_us": round(h.get("sum", 0) / 1e3, 3),
            "p50_us": round(quantile_bound(buckets, 0.50) / 1e3, 3),
            "p99_us": round(quantile_bound(buckets, 0.99) / 1e3, 3),
            "buckets": buckets,
        }
    return out
