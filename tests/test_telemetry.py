"""The unified telemetry plane (DESIGN.md §15).

Covers the metric registry's sharded-merge guarantee (concurrent adds
never lose counts), stage-span nesting and summaries, trace-level spans
read back from a CPU ``jax.profiler`` trace, the stage taxonomy, the
off-level zero-allocation contract (``span()`` returns one singleton),
the ``telemetry`` readonly attr on every resource type, burst/scalar
protocol-accounting equality through :func:`record_burst_mix`,
cross-rank snapshot merging, and the SPMD hygiene scan benchmarks gate
their timing rows on.
"""
import dataclasses
import glob
import os
import re
import threading
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

import repro.core as C
from repro.core import telemetry as T
from repro.core.telemetry import NULL_SPAN


# ---------------------------------------------------------------------------
# metric registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_concurrent_shard_merge_loses_nothing(self):
        reg = T.MetricRegistry()
        n_threads, per = 4, 10_000

        def worker():
            for _ in range(per):
                reg.add("msgs")
                reg.observe("lat", 7)

        threads = [threading.Thread(target=worker)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        snap = reg.snapshot()
        assert snap["counters"]["msgs"] == n_threads * per
        assert snap["hists"]["lat"]["count"] == n_threads * per
        assert snap["hists"]["lat"]["sum"] == n_threads * per * 7

    def test_dead_threads_shards_survive(self):
        reg = T.MetricRegistry()
        t = threading.Thread(target=lambda: reg.add("x", 5))
        t.start()
        t.join()
        assert reg.snapshot()["counters"]["x"] == 5

    def test_histogram_log2_buckets_and_quantiles(self):
        h = T.Histogram()
        for v in (0, 1, 2, 3, 1000):
            h.record(v)
        d = h.as_dict()
        assert d["count"] == 5 and d["sum"] == 1006
        # value 1000 has bit_length 10 -> bucket "10"
        assert d["buckets"]["10"] == 1
        assert T.quantile_bound(d["buckets"], 0.99) == 2.0 ** 10

    def test_gauges_sampled_at_snapshot(self):
        reg = T.MetricRegistry()
        state = {"v": 1}
        reg.register_gauge("depth", lambda: state["v"])
        assert reg.snapshot()["counters"]["depth"] == 1
        state["v"] = 9
        assert reg.snapshot()["counters"]["depth"] == 9


# ---------------------------------------------------------------------------
# spans + levels
# ---------------------------------------------------------------------------

class TestSpans:
    def test_off_level_returns_the_null_span_singleton(self):
        tele = T.Telemetry("off")
        assert tele.span("a") is tele.span("b") is NULL_SPAN
        tele.add("x")                         # no-op, no error
        snap = tele.snapshot()
        assert snap["counters"] == {} and snap["spans"] == {}

    def test_level_booleans_compose_upward(self):
        for level, (c, t, tr) in {
            "off": (False, False, False),
            "counters": (True, False, False),
            "timers": (True, True, False),
            "trace": (True, True, True),
        }.items():
            tele = T.Telemetry(level)
            assert (tele.counters_on, tele.timers_on, tele.trace_on) == \
                (c, t, tr), level
        with pytest.raises(ValueError):
            T.Telemetry("loud")

    def test_span_records_and_nests(self):
        tele = T.Telemetry("timers")
        with tele.span("outer"):
            with tele.span("inner"):
                time.sleep(0.001)
        spans = tele.snapshot()["spans"]
        assert spans["outer"]["count"] == spans["inner"]["count"] == 1
        # containment: the outer stage strictly encloses the inner one
        assert spans["outer"]["sum"] >= spans["inner"]["sum"] > 0

    def test_summarize_spans_shape(self):
        tele = T.Telemetry("timers")
        with tele.span("s"):
            pass
        out = T.summarize_spans(tele.snapshot()["spans"])
        row = out["s"]
        assert {"count", "total_us", "p50_us", "p99_us",
                "buckets"} <= row.keys()
        assert row["count"] == 1 and row["p99_us"] >= row["p50_us"] > 0


# ---------------------------------------------------------------------------
# trace level: the spans in the profiler's own trace
# ---------------------------------------------------------------------------

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _profiled(fn, tmp_path):
    """Run ``fn`` under a CPU ``jax.profiler`` session; returns the host
    plane's events as (name, start_ns, end_ns, line) tuples, one line per
    thread, numbered in the plane's order."""
    with jax.profiler.trace(str(tmp_path)):
        fn()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    return [(e.name, e.start_ns, e.end_ns, i)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for i, line in enumerate(plane.lines) for e in line.events]


class TestProfilerTrace:
    def test_nested_spans_land_in_the_host_plane(self, tmp_path):
        tele = T.Telemetry("trace")

        def work():
            with tele.span("sched.step"):
                with tele.span("sched.decode"):
                    time.sleep(0.001)

        events = {e[0]: e for e in _profiled(work, tmp_path)}
        outer, inner = events["sched.step"], events["sched.decode"]
        assert outer[1] <= inner[1] < inner[2] <= outer[2]
        assert inner[2] - inner[1] >= 1_000_000        # the 1 ms sleep
        # the histograms still record, as at timers level
        spans = tele.snapshot()["spans"]
        assert spans["sched.step"]["count"] == spans["sched.decode"][
            "count"] == 1

    def test_spans_from_two_threads_both_land(self, tmp_path):
        tele = T.Telemetry("trace")

        def work():
            t = threading.Thread(target=lambda: tele.span("worker.sweep")
                                 .__enter__().__exit__(None, None, None))
            with tele.span("post"):
                t.start()
                t.join()

        events = _profiled(work, tmp_path)
        lines = {e[0]: e[3] for e in events}
        assert {"post", "worker.sweep"} <= set(lines)
        assert lines["post"] != lines["worker.sweep"]

    def test_trace_level_cluster_shows_core_stages(self, tmp_path):
        cl = C.LocalCluster(2, attrs={"telemetry_level": "trace",
                                      "eager_max_bytes": 1,
                                      "packets_per_lane": 64})
        names = {e[0] for e in _profiled(lambda: _drive(cl, iters=8),
                                         tmp_path)}
        stages = names & set(T.STAGES)
        assert len(stages) >= 8, stages
        assert {"post", "post_burst", "progress", "transport.push",
                "cq.pop"} <= stages

    @pytest.mark.parametrize("level", ["off", "counters", "timers"])
    def test_levels_below_trace_leave_no_stage(self, level, tmp_path):
        cl = C.LocalCluster(2, attrs={"telemetry_level": level,
                                      "eager_max_bytes": 1})
        names = {e[0] for e in _profiled(lambda: _drive(cl, iters=8),
                                         tmp_path)}
        assert not names & set(T.STAGES)

    def test_every_span_in_the_program_is_a_known_stage(self):
        used = set()
        for path in glob.glob(os.path.join(SRC, "**", "*.py"),
                              recursive=True):
            with open(path) as f:
                used |= set(re.findall(r"span\(\s*\"([^\"]+)\"", f.read()))
        assert len(used) >= 20, used
        assert used <= set(T.STAGES), used - set(T.STAGES)


# ---------------------------------------------------------------------------
# snapshot merge (the SPMD fragment aggregation)
# ---------------------------------------------------------------------------

class TestMerge:
    def test_merge_snapshots_sums_elementwise(self):
        a = {"level": "counters", "counters": {"x": 1, "y": 2},
             "spans": {"post": {"count": 1, "sum": 10,
                                "buckets": {"4": 1}}}}
        b = {"level": "timers", "counters": {"x": 5},
             "spans": {"post": {"count": 2, "sum": 30,
                                "buckets": {"4": 1, "5": 1}}}}
        out = T.merge_snapshots([a, b, None])
        assert out["level"] == "timers"       # deepest level wins
        assert out["counters"] == {"x": 6, "y": 2}
        assert out["spans"]["post"] == {"count": 3, "sum": 40,
                                        "buckets": {"4": 2, "5": 1}}

    def test_render_block_sorts_and_summarizes(self):
        out = T.render_block({"level": "timers", "counters": {"b": 1, "a": 2},
                              "spans": {"s": {"count": 1, "sum": 2000,
                                              "buckets": {"11": 1}}}})
        assert list(out["counters"]) == ["a", "b"]
        assert out["spans"]["s"]["total_us"] == 2.0


# ---------------------------------------------------------------------------
# burst/scalar accounting equality (the unified record helper)
# ---------------------------------------------------------------------------

class TestRecordBurstMix:
    def test_matches_per_message_scalar_accounting(self):
        protos = [C.Protocol.INJECT, C.Protocol.INJECT, C.Protocol.BUFCOPY,
                  C.Protocol.ZEROCOPY, C.Protocol.BUFCOPY]
        sizes = [8, 8, 512, 1 << 21, 600]
        a, b = C.ProtocolStats(), C.ProtocolStats()
        T.record_burst_mix(a, protos, sizes, n=4)     # drop the suffix row
        for proto, size in zip(protos[:4], sizes[:4]):
            b.record_many(proto, 1, size)
        assert dataclasses.asdict(a) == dataclasses.asdict(b)

    def test_uniform_fast_path_and_registry_mirror(self):
        reg = T.MetricRegistry()
        stats = C.ProtocolStats()
        T.record_burst_mix(stats, [C.Protocol.INJECT] * 3, 8, 3,
                           registry=reg)
        assert stats.inject_msgs == 3 and stats.inject_bytes == 24
        counters = reg.snapshot()["counters"]
        assert counters["proto.inject.msgs"] == 3
        assert counters["proto.inject.bytes"] == 24
        T.record_burst_mix(stats, [C.Protocol.INJECT], 8, 0, registry=reg)
        assert stats.inject_msgs == 3         # n=0 records nothing


# ---------------------------------------------------------------------------
# the wired runtime: attr control, per-resource blocks, stage coverage
# ---------------------------------------------------------------------------

def _drive(cl, iters=48):
    """Mixed scalar + burst traffic through every instrumented stage."""
    r0, r1 = cl[0], cl[1]
    cq = r1.alloc_cq()
    rc = r1.register_rcomp(cq)
    payload = np.zeros(8, np.uint8)
    descs = [C.CommDesc(C.CommKind.AM, 1, payload, size=8, remote_comp=rc)
             for _ in range(4)]
    for i in range(iters):
        if i % 2:
            C.post_am(r0, 1, payload, remote_comp=rc)
        else:
            r0.post_many(descs)
        r1.progress()
        r0.progress()
        while cq.pop().is_done():
            pass
    cl.quiesce()
    while cq.pop().is_done():
        pass


class TestWiredRuntime:
    def test_telemetry_attr_on_every_resource_type(self):
        cl = C.LocalCluster(2, attrs={"telemetry_level": "counters"})
        rt = cl[0]
        eps = cl.alloc_endpoint(n_devices=1, name="tele")
        resources = {
            "cluster": cl,
            "runtime": rt,
            "device": rt.default_device,
            "endpoint": eps[0],
            "pool": rt.packet_pool,
            "matching": rt.matching,
            "cq": rt.alloc_cq(),
            "tscq": rt.alloc_cq(threadsafe=True),
            "workers": C.ProgressWorkerPool.for_runtime(rt),
            "fabric": cl.fabric,
        }
        for kind, res in resources.items():
            block = res.get_attr("telemetry")
            assert block == res.attrs["telemetry"], kind
            assert block["level"] == "counters", (kind, block)
            assert "counters" in block, kind

    def test_resource_blocks_reflect_traffic(self):
        cl = C.LocalCluster(2, attrs={"telemetry_level": "counters",
                                      "eager_max_bytes": 1})
        _drive(cl, iters=8)
        dev = cl[0].default_device.get_attr("telemetry")["counters"]
        assert dev["device.posts"] > 0 and dev["device.pushes"] > 0
        pool = cl[0].packet_pool.get_attr("telemetry")["counters"]
        assert pool["pool.gets"] > 0
        fab = cl.fabric.get_attr("telemetry")["counters"]
        assert fab["fabric.pushes"] > 0
        assert fab["fabric.in_flight"] == 0    # quiesced

    def test_timers_run_covers_at_least_eight_stages(self):
        cl = C.LocalCluster(2, attrs={"telemetry_level": "timers",
                                      "eager_max_bytes": 1,
                                      "packets_per_lane": 64})
        _drive(cl)
        snap = cl.telemetry_snapshot()
        assert snap["level"] == "timers"
        stages = set(snap["spans"])
        assert {"post", "post_burst", "progress", "progress.drain",
                "transport.push", "transport.drain", "pool.get",
                "cq.pop"} <= stages, stages
        assert len(stages) >= 8
        # the unified counter surface rides the same snapshot
        assert snap["counters"]["device.posts"] > 0
        assert snap["counters"]["engine.passes"] > 0

    def test_off_level_records_no_spans_but_keeps_legacy_counters(self):
        cl = C.LocalCluster(2, attrs={"telemetry_level": "off"})
        _drive(cl, iters=8)
        assert cl[0].tele.span("post") is NULL_SPAN
        snap = cl.telemetry_snapshot()
        assert snap["spans"] == {}
        # legacy counters (always on) still surface through collectors
        assert snap["counters"]["device.posts"] > 0

    def test_worker_pool_spans(self):
        cl = C.LocalCluster(2, attrs={"telemetry_level": "timers"})
        with C.ProgressWorkerPool.for_cluster(cl, n_workers=1):
            time.sleep(0.05)
        spans = cl.telemetry_snapshot()["spans"]
        assert "worker.sweep" in spans
        assert "worker.nap" in spans          # idle fabric -> backoff naps

    def test_runtimes_share_the_cluster_hub(self):
        cl = C.LocalCluster(2, attrs={"telemetry_level": "timers"})
        assert cl[0].tele is cl.tele is cl[1].tele   # one hub per cluster
        assert cl[0].tele.timers_on
        assert cl[0].get_attr("telemetry_level") == "timers"
        # merged cluster snapshot dedups the shared hub (no double count)
        cq = cl[1].alloc_cq()
        rc = cl[1].register_rcomp(cq)
        C.post_am(cl[0], 1, np.zeros(8, np.uint8), remote_comp=rc)
        posts = cl.telemetry_snapshot()["counters"]["device.posts"]
        assert posts == sum(d.posts for rt in cl.runtimes
                            for d in rt.devices)

    def test_env_layer_controls_the_level(self, monkeypatch):
        monkeypatch.setenv("REPRO_ATTR_TELEMETRY_LEVEL", "counters")
        cl = C.LocalCluster(2)
        assert cl.tele.counters_on and not cl.tele.timers_on
        assert cl.get_attr("telemetry_level") == "counters"


# ---------------------------------------------------------------------------
# SPMD hygiene (the timing-row gate)
# ---------------------------------------------------------------------------

class TestHygiene:
    def test_fake_stale_session_detected(self, tmp_path):
        from repro.launch import spmd
        (tmp_path / "repro-spmd-dead0").mkdir()
        (tmp_path / "unrelated-dir").mkdir()
        rep = spmd.hygiene_report(roots=[str(tmp_path)])
        assert not rep["clean"]
        assert rep["stale_sessions"] == \
            [str(tmp_path / "repro-spmd-dead0")]
        assert isinstance(rep["orphans"], list)

    def test_preflight_strict_raises_and_env_overrides(self, tmp_path,
                                                       monkeypatch):
        from repro.launch import spmd
        (tmp_path / "repro-spmd-dead1").mkdir()
        monkeypatch.delenv(spmd.ALLOW_DIRTY_ENV, raising=False)
        with pytest.raises(RuntimeError, match="hygiene"):
            spmd.preflight(strict=True, roots=[str(tmp_path)])
        monkeypatch.setenv(spmd.ALLOW_DIRTY_ENV, "1")
        rep = spmd.preflight(strict=True, roots=[str(tmp_path)])
        assert not rep["clean"]               # reported, not fatal

    def test_clean_root_passes(self, tmp_path):
        from repro.launch import spmd
        rep = spmd.preflight(strict=True, roots=[str(tmp_path)])
        assert rep["clean"]
