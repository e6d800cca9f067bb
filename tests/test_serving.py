"""The continuous-batching serving subsystem (DESIGN.md §17).

Covers the packed ResultTokens layout, slot/page admission through the
attr chain (validation at alloc, ``get_attr`` introspection), the
engine's end-to-end exactly-once token contract — including the
hypothesis property over interleaved prefill-insert/decode/drain with
thread-safe CQs, two drain workers, and ``chaos_drop`` faults — plus the
burst result-delivery path in the legacy scheduler and the coalescing
socket flush (satellites of the same PR).
"""
import errno
import time

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import attrs as A
from repro.core.runtime import LocalCluster
from repro.core.status import FatalError, done, retry
from repro.core.telemetry import NULL_TELEMETRY, Telemetry
from repro.core.transport.socket import SocketTransport
from repro.core.transport.wire import WireKind, WireMsg
from repro.serving import (ContinuousBatcher, PagedKVAllocator, ResultDrain,
                           ResultTokens, ServePlane, ServeScheduler,
                           ServeTransport, SlotAllocator, SlotData,
                           SyntheticModel, TokenClient, decode_token_row,
                           encode_token_row)
from repro.serving.batching import EOT_MAX_NEW
from repro.serving.slots import SERVING_ATTRS


# ---------------------------------------------------------------------------
# ResultTokens: the packed per-step array
# ---------------------------------------------------------------------------

class TestResultTokens:
    def test_pack_and_slot_views(self):
        rt = ResultTokens.pack(slots=[0, 2], rids=[7, 9],
                               tokens=[11, 13], lengths=[1, 4],
                               dones=[0, 1], n_slots=4)
        assert rt.n_slots == 4
        assert list(rt.active_slots()) == [0, 2]
        s2 = rt.get_result_at_slot(2)
        assert isinstance(s2, SlotData)
        assert s2.tokens[0] == 13 and s2.valid[0] == 1 and s2.lengths[0] == 4
        assert rt.get_result_at_slot(1).valid[0] == 0

    def test_wire_rows_roundtrip(self):
        rt = ResultTokens.pack(slots=[1, 3], rids=[5, 6],
                               tokens=[100, 200], lengths=[3, 1],
                               dones=[1, 0], n_slots=4)
        rows = rt.wire_rows()
        assert [rid for rid, _ in rows] == [5, 6]
        # row = [rid, seq, token, done]; seq == length - 1
        assert decode_token_row(rows[0][1]) == (5, 2, 100, 1)
        assert decode_token_row(rows[1][1]) == (6, 0, 200, 0)
        # uniform 16-byte rows: the fused-doorbell eligibility contract
        assert {r.nbytes for _, r in rows} == {16}

    def test_rejects_bad_shape_and_row(self):
        with pytest.raises(ValueError):
            ResultTokens(np.zeros((4, 3), np.int32))
        with pytest.raises(ValueError):
            decode_token_row(b"\x00" * 12)
        assert decode_token_row(encode_token_row(1, 2, 3, 1)) == (1, 2, 3, 1)


# ---------------------------------------------------------------------------
# slot allocator: admission through the attr chain
# ---------------------------------------------------------------------------

class TestSlotAllocator:
    def test_attrs_validate_at_alloc(self):
        with pytest.raises(A.AttrError, match="kv_slots"):
            SlotAllocator(kv_slots=0)
        with pytest.raises(A.AttrError, match="kv_page_tokens"):
            SlotAllocator(kv_page_tokens=-1)
        with pytest.raises(A.AttrError, match="kv_evict"):
            SlotAllocator(kv_evict="lru")

    def test_env_layer_reaches_allocator(self, monkeypatch):
        monkeypatch.setenv("REPRO_ATTR_KV_SLOTS", "3")
        monkeypatch.setenv("REPRO_ATTR_KV_EVICT", "preempt_longest")
        sa = SlotAllocator()
        assert sa.n_slots == 3
        assert sa.evict_policy == "preempt_longest"
        assert sa.get_attr("kv_slots") == 3
        monkeypatch.setenv("REPRO_ATTR_KV_EVICT", "bogus")
        with pytest.raises(A.AttrError, match="kv_evict"):
            SlotAllocator()

    def test_get_attr_surface(self):
        sa = SlotAllocator(kv_slots=2, kv_page_tokens=4, kv_pages=6)
        assert sa.get_attr("kv_pages") == 6
        assert sa.get_attr("free_slots") == 2
        assert sa.get_attr("occupancy") == 0.0
        echo = sa.attrs_echo()
        assert echo["values"]["kv_slots"] == 2
        assert echo["sources"]["kv_slots"] == "resource"
        assert echo["sources"]["kv_evict"] == "default"
        assert echo["sources"]["occupancy"] == "discovered"
        with pytest.raises(A.AttrError, match="nope"):
            sa.get_attr("nope")

    def test_admission_is_ternary_and_all_or_nothing(self):
        sa = SlotAllocator(kv_slots=2, kv_page_tokens=4, kv_pages=4)
        st = sa.admit(1, 8)             # 2 pages
        assert st.is_done() and st.value == 0
        assert sa.admit(2, 9).is_retry()   # needs 3 pages, 2 left
        assert sa.get_attr("free_pages") == 2   # rollback left them free
        assert sa.admit(2, 8).is_done()
        assert sa.admit(3, 4).is_retry()   # no slot left
        with pytest.raises(ValueError):
            sa.admit(1, 4)                  # double admit
        sa.release(1)
        assert sa.occupancy() == 0.5
        assert sa.admit(3, 4).is_done()
        assert sa.counters()["rejections"] == 2

    def test_victim_is_largest_footprint(self):
        sa = SlotAllocator(kv_slots=4, kv_page_tokens=4,
                           kv_evict="preempt_longest")
        for rid, tokens in ((1, 4), (2, 20), (3, 8)):
            assert sa.admit(rid, tokens).is_done()
        assert sa.victim() == 2
        refuse = SlotAllocator(kv_slots=4, kv_page_tokens=4)
        refuse.admit(1, 20)
        assert refuse.victim() is None     # policy "refuse" never evicts


# ---------------------------------------------------------------------------
# the engine end to end (single process, both roles on one cluster)
# ---------------------------------------------------------------------------

def _drive(server, client, specs, *, step_every=1, deadline_s=30.0):
    """Submit (prompt_len, max_new) specs open-loop and drain to empty."""
    rng = np.random.default_rng(1234)
    for i, (plen, max_new) in enumerate(specs):
        prompt = rng.integers(0, 1000, plen).astype(np.int32)
        rid, stat = client.submit(prompt, max_new)
        tries = 0
        while stat.is_retry():
            client.pump()
            server.step()
            tries += 1
            assert tries < 2000, "submit never accepted"
            rid, stat = client.submit(prompt, max_new, rid=rid)
        if i % step_every == 0:
            server.step()
    # an accepted prompt may still be in retransmit flight under chaos —
    # the server must keep stepping until it has *finished* every one
    t0 = time.monotonic()
    while not (server.completed >= len(specs) and server.idle):
        server.step()
        assert time.monotonic() - t0 < deadline_s, (
            f"server stalled: {server.counters()}")
    while client.drain.drained < client.expected_tokens:
        client.pump()
        if time.monotonic() - t0 > deadline_s:
            break
    return client.collect()


def _assert_exactly_once(report, n_requests):
    assert report["completed"] == n_requests
    assert report["lost"] == 0
    assert report["duplicated"] == 0
    assert report["mismatched"] == 0
    assert report["out_of_order"] == 0
    assert report["bad_done"] == 0
    assert report["unexpected"] == 0


class TestContinuousBatcher:
    def test_serve_roundtrip_exactly_once(self):
        cluster = LocalCluster(2)
        try:
            plane = ServePlane(cluster)
            model = SyntheticModel(seed=7)
            server = ContinuousBatcher(plane, model, kv_slots=4,
                                       kv_page_tokens=8, prefill_chunk=16)
            client = TokenClient(plane, model, drain_workers=2)
            specs = [(30, 8), (1, 1), (64, 4), (5, 12), (17, 3),
                     (40, 6), (2, 9), (33, 1)]
            report = _drive(server, client, specs)
            _assert_exactly_once(report, len(specs))
            assert report["tokens"] == sum(m for _, m in specs)
            assert len(report["ttft_s"]) == len(specs)
            assert server.slots.occupancy() == 0.0
        finally:
            cluster.close()

    def test_engine_attr_chain_and_introspection(self):
        cluster = LocalCluster(2, attrs={"kv_slots": 6, "prefill_chunk": 4})
        try:
            plane = ServePlane(cluster)
            server = ContinuousBatcher(plane, SyntheticModel(),
                                       max_batch=5)
            # runtime-config layer reached the engine; override beat it
            assert server.get_attr("kv_slots") == 6
            assert server.get_attr("prefill_chunk") == 4
            assert server.get_attr("max_batch") == 5
            for name in SERVING_ATTRS:
                server.get_attr(name)          # every serving attr answers
            assert server.get_attr("active_requests") == 0
            assert server.get_attr("occupancy") == 0.0
            echo = server.attrs_echo()
            assert echo["sources"]["kv_slots"] == "runtime"
            assert echo["sources"]["max_batch"] == "resource"
            with pytest.raises(A.AttrError, match="kv_page_tokens"):
                ContinuousBatcher(plane, SyntheticModel(), kv_page_tokens=0)
        finally:
            cluster.close()

    def test_zero_means_derived_geometry(self):
        cluster = LocalCluster(2)
        try:
            plane = ServePlane(cluster)
            server = ContinuousBatcher(plane, SyntheticModel(), kv_slots=3)
            assert server.slots.n_pages == 24      # kv_pages=0 -> 8/slot
            assert server.max_batch == 3           # max_batch=0 -> kv_slots
        finally:
            cluster.close()

    def test_preempt_longest_never_duplicates(self):
        cluster = LocalCluster(2)
        try:
            plane = ServePlane(cluster)
            model = SyntheticModel(seed=2)
            # 6 pages of 2 tokens: one long request hogs the pool until
            # admission preempts it for the short ones
            server = ContinuousBatcher(plane, model, kv_slots=3,
                                       kv_page_tokens=2, kv_pages=6,
                                       kv_evict="preempt_longest",
                                       prefill_chunk=4)
            client = TokenClient(plane, model, drain_workers=2)
            specs = [(4, 6), (2, 2), (2, 2), (1, 3), (2, 1)]
            report = _drive(server, client, specs, step_every=2,
                            deadline_s=40.0)
            _assert_exactly_once(report, len(specs))
            assert server.slots.preemptions > 0
        finally:
            cluster.close()

    def test_refuse_policy_backlogs_instead(self):
        cluster = LocalCluster(2)
        try:
            plane = ServePlane(cluster)
            model = SyntheticModel(seed=4)
            server = ContinuousBatcher(plane, model, kv_slots=1,
                                       kv_page_tokens=4)
            client = TokenClient(plane, model, drain_workers=2)
            specs = [(8, 4)] * 5
            report = _drive(server, client, specs)
            _assert_exactly_once(report, len(specs))
            assert server.slots.preemptions == 0
            assert server.counters()["backlog_max_depth"] > 0
        finally:
            cluster.close()

    def test_plane_requires_distinct_ranks_and_first_rcomp(self):
        cluster = LocalCluster(2)
        try:
            with pytest.raises(FatalError, match="distinct"):
                ServePlane(cluster, client_rank=0, server_rank=0)
            # steal handle 0 on the server runtime: the handshake
            # convention must fail loudly, not deliver to the wrong CQ
            cluster[1].register_rcomp(cluster[1].alloc_cq())
            with pytest.raises(FatalError, match="first"):
                ServePlane(cluster)
        finally:
            cluster.close()


# ---------------------------------------------------------------------------
# satellite: the exactly-once property under interleaving + chaos
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(st.lists(st.tuples(st.integers(min_value=1, max_value=24),
                          st.integers(min_value=1, max_value=8)),
                min_size=1, max_size=10),
       st.integers(min_value=1, max_value=4),
       st.booleans())
def test_property_interleaved_serve_exactly_once(specs, step_every, chaos):
    """Interleaved prefill-insert/decode/drain with thread-safe CQs and 2
    drain workers never drops, duplicates, or reorders a client's token
    stream — with or without chaos_drop=0.05 underneath."""
    attrs = {"chaos_drop": 0.05, "chaos_seed": 99} if chaos else {}
    cluster = LocalCluster(2, attrs=attrs)
    try:
        plane = ServePlane(cluster)
        model = SyntheticModel(seed=len(specs))
        server = ContinuousBatcher(plane, model, kv_slots=2,
                                   kv_page_tokens=4, prefill_chunk=8)
        client = TokenClient(plane, model, drain_workers=2)
        report = _drive(server, client, specs, step_every=step_every,
                        deadline_s=60.0)
        _assert_exactly_once(report, len(specs))
    finally:
        cluster.close()


# ---------------------------------------------------------------------------
# telemetry spans on every stage
# ---------------------------------------------------------------------------

def test_stage_spans_cover_the_pipeline():
    cluster = LocalCluster(2, attrs={"telemetry_level": "timers"})
    try:
        plane = ServePlane(cluster)
        model = SyntheticModel(seed=1)
        server = ContinuousBatcher(plane, model, kv_slots=4)
        client = TokenClient(plane, model, drain_workers=2)
        report = _drive(server, client, [(20, 4), (3, 2)])
        _assert_exactly_once(report, 2)
        from repro.core.telemetry import render_block
        spans = render_block(cluster.tele.snapshot())["spans"]
        for stage in ("serve.enqueue", "serve.prefill", "serve.insert",
                      "serve.decode", "serve.deliver", "serve.drain"):
            assert spans.get(stage, {}).get("count", 0) > 0, stage
    finally:
        cluster.close()


# ---------------------------------------------------------------------------
# satellite: scheduler result delivery rides post_am_many
# ---------------------------------------------------------------------------

class TestSchedulerBurstDelivery:
    def _serve(self, cluster, **kw):
        transport = ServeTransport(cluster)
        alloc = PagedKVAllocator(n_pages=64, page_size=8)
        sched = ServeScheduler(
            lambda toks, pos: (toks + 1) % 997, max_batch=8,
            allocator=alloc, transport=transport, **kw)
        return transport, sched

    def test_remote_results_arrive_in_one_burst(self):
        cluster = LocalCluster(2)
        try:
            transport, sched = self._serve(cluster)
            rids = [sched.submit_remote(np.arange(4, dtype=np.int32), 3)
                    for _ in range(6)]
            got = {}
            for _ in range(200):
                sched.step()
                transport.pump()
                for rid, toks in transport.poll_results():
                    got[rid] = toks
                if len(got) == len(rids):
                    break
            assert set(got) == set(rids)
            assert all(len(t) == 3 for t in got.values())
            assert sched.completed == len(rids)
            assert not sched._pending_sends and not sched._outbox
        finally:
            cluster.close()

    def test_retry_rejected_sends_park_in_order(self):
        cluster = LocalCluster(2)
        try:
            transport, sched = self._serve(cluster)
            # jam the wire: statuses come back retry, results must park
            real = transport.send_results
            transport.send_results = lambda batch: [retry()
                                                    for _ in batch]
            for _ in range(3):
                sched.submit_remote(np.arange(2, dtype=np.int32), 2)
            for _ in range(40):
                sched.step()
                transport.pump()
                if sched.completed == 3:
                    break
            assert len(sched._pending_sends) == 3     # parked, never lost
            order = [rid for rid, _ in sched._pending_sends]
            # un-jam: the parked batch redelivers, in order, via the burst
            transport.send_results = real
            got = []
            for _ in range(200):
                sched.step()
                transport.pump()
                got += transport.poll_results()
                if len(got) == 3:
                    break
            assert [rid for rid, _ in got] == order
            assert not sched._pending_sends
        finally:
            cluster.close()


# ---------------------------------------------------------------------------
# scheduler and transport telemetry: sched.* spans, serve.result_wire
# ---------------------------------------------------------------------------

class TestSchedulerTelemetry:
    def _run(self, cluster, tele=None, n=5, max_new=(1, 2, 3, 4, 6)):
        """Serve ``n`` remote requests; returns (rounds, decode calls,
        results polled, scheduler, transport)."""
        kw = {} if tele is None else {"tele": tele}
        transport = ServeTransport(cluster, **kw)
        calls = []

        def decode_fn(toks, pos):
            calls.append(len(toks))
            return (toks + 1) % 997

        sched = ServeScheduler(decode_fn, max_batch=8,
                               allocator=PagedKVAllocator(n_pages=64,
                                                          page_size=8),
                               transport=transport)
        rids = [sched.submit_remote(np.arange(3, dtype=np.int32), m)
                for m in max_new[:n]]
        got, rounds = [], 0
        while len(got) < len(rids) and rounds < 200:
            sched.step()
            transport.pump()
            got += transport.poll_results()
            rounds += 1
        # a few idle rounds: they step but make no decode call
        for _ in range(3):
            sched.step()
            rounds += 1
        assert sorted(r for r, _ in got) == sorted(rids)
        return rounds, len(calls), len(got), sched, transport

    def test_span_counts_match_rounds_and_decode_calls(self):
        cluster = LocalCluster(2)
        try:
            tele = Telemetry("timers")
            rounds, calls, _, sched, transport = self._run(cluster, tele)
            assert sched.tele is tele and transport.tele is tele
            spans = tele.snapshot()["spans"]
            assert set(spans) == {"sched.step", "sched.decode"}
            assert spans["sched.step"]["count"] == rounds
            assert spans["sched.decode"]["count"] == calls < rounds
            assert spans["sched.step"]["sum"] >= spans["sched.decode"]["sum"]
        finally:
            cluster.close()

    @pytest.mark.parametrize("level", ["counters", "timers"])
    def test_result_wire_counts_every_polled_result(self, level):
        cluster = LocalCluster(2)
        try:
            tele = Telemetry(level)
            _, _, polled, _, transport = self._run(cluster, tele)
            wire = tele.snapshot()["hists"]["serve.result_wire"]
            assert wire["count"] == polled == 5
            assert wire["sum"] > 0
            assert not transport._posted_ns      # every stamp consumed
        finally:
            cluster.close()

    def test_default_hub_is_the_cluster_hub_and_records_nothing(self):
        cluster = LocalCluster(2, attrs={"telemetry_level": "off"})
        try:
            _, _, _, sched, transport = self._run(cluster)
            assert sched.tele is transport.tele is cluster.tele
            snap = cluster.tele.snapshot()
            assert snap["spans"] == {} and snap["hists"] == {}
            assert not transport._posted_ns
        finally:
            cluster.close()

    def test_scheduler_without_transport_defaults_to_the_null_hub(self):
        sched = ServeScheduler(lambda t, p: t, max_batch=2,
                               allocator=PagedKVAllocator(n_pages=4,
                                                          page_size=8))
        assert sched.tele is NULL_TELEMETRY
        sched.submit(np.arange(2, dtype=np.int32), 2)
        sched.step()
        assert NULL_TELEMETRY.snapshot()["spans"] == {}


# ---------------------------------------------------------------------------
# ResultDrain: stamps and per-worker streams
# ---------------------------------------------------------------------------

def test_result_drain_stamps_and_worker_results():
    cluster = LocalCluster(1)
    try:
        cq = cluster[0].alloc_cq(threadsafe=True)
        drain = ResultDrain(cq, 2, stamp=True).start()
        t0 = time.perf_counter()
        for i in range(50):
            cq.signal(done(np.int32(i), tag=i))
        deadline = time.monotonic() + 5
        while drain.drained < 50 and time.monotonic() < deadline:
            time.sleep(0.001)
        results = drain.stop()
        assert len(results) == 50
        assert sorted(st.tag for st in results) == list(range(50))
        chunks = drain.worker_results()
        assert len(chunks) == 3            # 2 workers + final sweep
        for chunk in chunks:
            for st_, stamp in chunk:
                assert stamp >= t0
    finally:
        cluster.close()


# ---------------------------------------------------------------------------
# satellite: socket flush coalescing with depth accounting
# ---------------------------------------------------------------------------

def _am(tag, dst=1):
    return WireMsg(WireKind.EAGER_AM, 0, dst, tag=tag,
                   payload=np.full(8, tag % 250, np.uint8), size=8, rcomp=0)


class _ThrottledSock:
    """Fake kernel socket: accepts at most ``cap`` bytes per send."""

    def __init__(self):
        self.cap = 0
        self.calls = []

    def send(self, blob):
        n = min(self.cap, len(blob))
        if n == 0:
            raise OSError(errno.EAGAIN, "would block")
        self.calls.append((len(blob), n))
        return n

    def close(self):
        pass


class TestSocketFlushCoalescing:
    def test_one_send_per_burst_with_depth_accounting(self, tmp_path):
        t = SocketTransport(2, rank=0, session=str(tmp_path / "s"))
        try:
            fake = _ThrottledSock()
            t._out[1] = fake
            for i in range(10):
                assert t.try_push(_am(i))     # EAGAIN: all stay buffered
            key = (1, 0)
            assert t._tx_weight[key] == 10 and len(t._txq[1]) == 10
            fake.cap = 1 << 20
            with t._lock:
                t._flush(1)
            assert len(fake.calls) == 1       # writev-style: ONE syscall
            assert t._tx_weight[key] == 0 and not t._txq[1]
            assert t._tx_flush_frames == 10
            assert t.get_attr("socket_flush_batches") >= 1
            assert t.get_attr("socket_flush_frames") == 10
        finally:
            t.close()

    def test_partial_send_reslices_head_only(self, tmp_path):
        t = SocketTransport(2, rank=0, session=str(tmp_path / "s"))
        try:
            fake = _ThrottledSock()
            t._out[1] = fake
            for i in range(3):
                assert t.try_push(_am(i))
            frames = [f for f, _, _ in t._txq[1]]
            key = (1, 0)
            # accept frame0 fully plus 3 bytes of frame1
            fake.cap = len(frames[0]) + 3
            with t._lock:
                t._flush(1)
            assert t._tx_weight[key] == 2      # only frame0's weight freed
            q = list(t._txq[1])
            assert len(q) == 2
            assert len(q[0][0]) == len(frames[1]) - 3   # head re-sliced
            assert q[1][0] == frames[2]                 # tail untouched
            # drain the rest: accounting converges to zero
            fake.cap = 1 << 20
            with t._lock:
                t._flush(1)
            assert t._tx_weight[key] == 0 and not t._txq[1]
            assert t._tx_flush_frames == 3
        finally:
            t.close()

    def test_real_pair_burst_is_coalesced_and_intact(self, tmp_path):
        a = SocketTransport(2, rank=0, session=str(tmp_path / "pair"))
        b = SocketTransport(2, rank=1, session=str(tmp_path / "pair"))
        try:
            msgs = [_am(i) for i in range(20)]
            assert a.push_burst(msgs) == 20
            flushes = a._tx_flushes
            assert a._tx_flush_frames >= 20
            assert flushes < 20               # strictly fewer sends than frames
            got = []
            for _ in range(400):
                got += b.drain(1, 0)
                if len(got) == 20:
                    break
            assert [m.tag for m in got] == list(range(20))
            assert all(bytes(m.payload) == bytes(_am(m.tag).payload)
                       for m in got)
        finally:
            a.close()
            b.close()
