"""Batched data plane (paper §4.3): burst posting, burst progress, the
eager fast path, and the liveness/ordering guarantees that make batching
safe — doorbell splits preserve per-peer FIFO, the lock-free matching
probe never double-matches or drops, and burst signaling cannot wedge a
popper against a mid-ticket producer."""
import threading
import time

import numpy as np
import jax.numpy as jnp
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (CommConfig, CommDesc, CommKind, HostMatchingEngine,
                        HostPacketPool, LocalCluster, MatchKind,
                        PostBatch, ThreadSafeCompletionQueue, done,
                        free_count, init_pool, make_key, pool_get,
                        pool_get_n, post_am_x, post_many, post_recv_x,
                        post_send_x)
from repro.core.completion import CompletionQueue
from repro.core.progress.fabric import (Fabric, PackedBurst, WireKind,
                                        WireMsg, payloads_to_bytes)
from repro.core.status import ErrorCode


# ---------------------------------------------------------------------------
# Fabric: drain semantics (satellite) + push_burst
# ---------------------------------------------------------------------------

class TestFabricBurst:
    def _msg(self, i=0, dst=1, dev=0):
        return WireMsg("eager_am", 0, dst, tag=i, device_index=dev)

    def test_drain_limit_zero_means_all(self):
        fab = Fabric(2)
        for i in range(5):
            assert fab.try_push(self._msg(i))
        assert [m.tag for m in fab.drain(1, 0, 0)] == [0, 1, 2, 3, 4]

    def test_drain_positive_limit_caps_burst(self):
        fab = Fabric(2)
        for i in range(5):
            fab.try_push(self._msg(i))
        assert [m.tag for m in fab.drain(1, 0, 2)] == [0, 1]
        assert [m.tag for m in fab.drain(1, 0, 3)] == [2, 3, 4]

    def test_drain_negative_limit_raises(self):
        fab = Fabric(2)
        with pytest.raises(ValueError):
            fab.drain(1, 0, -1)

    def test_push_burst_accepts_prefix_on_full(self):
        fab = Fabric(2, depth=3)
        msgs = [self._msg(i) for i in range(5)]
        assert fab.push_burst(msgs) == 3
        assert fab.full_events == 1
        assert [m.tag for m in fab.drain(1, 0)] == [0, 1, 2]
        assert fab.push_burst(msgs[3:]) == 2

    def test_push_burst_one_telemetry_bump(self):
        fab = Fabric(2)
        fab.push_burst([self._msg(i) for i in range(8)])
        assert fab.pushes == 8

    def test_push_burst_rejects_mixed_streams(self):
        fab = Fabric(3)
        with pytest.raises(Exception):
            fab.push_burst([self._msg(0, dst=1), self._msg(1, dst=2)])

    def test_payloads_to_bytes_one_stacked_copy(self):
        bufs = [np.full(8, i, np.uint8) for i in range(6)]
        rows = payloads_to_bytes(bufs)
        assert len(rows) == 6
        # rows are views of one stacked base — a single burst-sized copy
        base = rows[0].base
        assert base is not None and all(r.base is base for r in rows)
        # snapshots: mutating the source after staging must not leak in
        bufs[2][:] = 99
        assert rows[2][0] == 2

    def test_payloads_to_bytes_ragged_falls_back(self):
        rows = payloads_to_bytes([np.zeros(4, np.uint8),
                                  np.zeros(8, np.uint8)])
        assert [r.nbytes for r in rows] == [4, 8]


class TestPackedDrainConsistency:
    """Satellite regression: row-weighted ``stream_depth``, ``ready``,
    and ``drain(limit=k)`` must agree on "quiet" when packed doorbells
    sit in the stream — historically only scalar pushes were covered
    here, and ``drain`` counted doorbells as one row."""

    def _packed(self, k, tag=0):
        data = np.arange(k * 8, dtype=np.uint8).reshape(k, 8)
        return WireMsg(WireKind.EAGER_PACKED_AM, src=0, dst=1, tag=tag,
                       payload=PackedBurst(data, np.full(k, 8, np.int64),
                                           [tag] * k, k),
                       size=int(data.nbytes), rcomp=0)

    def _scalar(self, tag=0):
        return WireMsg(WireKind.EAGER_AM, src=0, dst=1, tag=tag,
                       payload=np.zeros(8, np.uint8), size=8, rcomp=0)

    def test_drain_limit_is_row_weighted(self):
        fab = Fabric(2, depth=64)
        assert fab.try_push(self._scalar(tag=0))
        assert fab.push_packed(self._packed(5, tag=1)) == 5
        assert fab.try_push(self._scalar(tag=2))
        assert fab.stream_depth(1, 0) == 7
        # limit=2 admits the scalar then the WHOLE doorbell (doorbells
        # pop atomically, so a limit may overshoot mid-doorbell) ...
        out = fab.drain(1, 0, 2)
        assert [m.kind for m in out] == [WireKind.EAGER_AM,
                                         WireKind.EAGER_PACKED_AM]
        # ... and the released weight is 6 rows, not 2 messages
        assert fab.stream_depth(1, 0) == 1
        assert [m.tag for m in fab.drain(1, 0)] == [2]

    def test_limit_below_doorbell_weight_still_pops_it_whole(self):
        fab = Fabric(2, depth=64)
        fab.push_packed(self._packed(6))
        out = fab.drain(1, 0, 1)
        assert len(out) == 1 and out[0].payload.count == 6
        assert fab.stream_depth(1, 0) == 0

    def test_depth_ready_and_drain_agree_on_quiet(self):
        fab = Fabric(2, depth=64)
        assert not fab.ready(1, 0) and fab.stream_depth(1, 0) == 0
        fab.push_packed(self._packed(4))
        # the idle fast path and the depth probe agree: occupied
        assert fab.ready(1, 0) and fab.stream_depth(1, 0) == 4
        assert fab.in_flight() == 4 and fab.pending_to(1) == 4
        assert len(fab.drain(1, 0, 4)) == 1
        # all three views agree again: quiet
        assert not fab.ready(1, 0)
        assert fab.stream_depth(1, 0) == 0
        assert fab.in_flight() == 0 and fab.pending_to(1) == 0
        assert fab.drain(1, 0) == []

    def test_partial_drain_keeps_views_consistent(self):
        fab = Fabric(2, depth=64)
        for t in range(3):
            fab.push_packed(self._packed(3, tag=t))
        assert fab.stream_depth(1, 0) == 9
        assert len(fab.drain(1, 0, 3)) == 1       # exactly one doorbell
        assert fab.stream_depth(1, 0) == 6 and fab.ready(1, 0)
        assert len(fab.drain(1, 0, 4)) == 2       # 3 < 4, next fills it
        assert fab.stream_depth(1, 0) == 0 and not fab.ready(1, 0)


# ---------------------------------------------------------------------------
# Packet pool: burst get/put (host + jittable)
# ---------------------------------------------------------------------------

class TestPoolBurst:
    def test_get_n_one_lock_round_trip(self):
        pool = HostPacketPool(n_lanes=1, packets_per_lane=32)
        base = pool.locks[0].acquisitions
        ids, stt = pool.get_n(0, 16)
        assert stt.is_done() and len(ids) == len(set(ids)) == 16
        assert pool.locks[0].acquisitions == base + 1
        pool.put_n(0, ids)
        assert pool.locks[0].acquisitions == base + 2
        assert pool.free_packets() == 32

    def test_get_n_short_grab_is_retry_with_prefix(self):
        pool = HostPacketPool(n_lanes=1, packets_per_lane=4)
        ids, stt = pool.get_n(0, 10)
        assert stt.is_retry() and stt.code == ErrorCode.RETRY_NOPACKET
        assert len(ids) == 4                      # the doorbell-split prefix
        ids2, st2 = pool.get_n(0, 2)
        assert st2.is_retry() and ids2 == []

    def test_get_n_steals_across_lanes(self):
        pool = HostPacketPool(n_lanes=2, packets_per_lane=8)
        ids, stt = pool.get_n(0, 10)              # needs the victim's half
        assert len(ids) >= 8 and pool.steals == 1

    def test_get_n_zero_is_noop(self):
        pool = HostPacketPool(n_lanes=1, packets_per_lane=4)
        assert pool.get_n(0, 0) == ([], pool.get_n(0, 0)[1])
        assert pool.free_packets() == 4

    def test_pool_get_n_matches_sequential_gets(self):
        import jax
        p1 = init_pool(2, 8)
        p2 = init_pool(2, 8)
        burst_fn = jax.jit(pool_get_n, static_argnums=2)
        p1, ids, got, stt = burst_fn(p1, 0, 5, 3)
        seq = []
        for _ in range(5):
            p2, pid, s2 = pool_get(p2, 0, 3)
            assert int(s2) == 0
            seq.append(int(pid))
        assert int(got) == 5 and int(stt) == 0
        assert [int(i) for i in ids] == seq
        assert int(free_count(p1)) == int(free_count(p2))

    def test_pool_get_n_short_grab_pads(self):
        p = init_pool(1, 4)
        p, ids, got, stt = pool_get_n(p, 0, 6, 0)
        assert int(got) == 4 and int(stt) == 1
        assert [int(i) for i in ids[4:]] == [-1, -1]
        assert int(free_count(p)) == 0

    def test_pool_get_n_steal_clamped_to_lane_room(self):
        """Regression: stealing into a NON-empty lane must clamp the
        transfer to the lane's remaining room — an unclamped roll wraps
        live slots past lane_cap, duplicating ids and losing others."""
        p = init_pool(2, 8, lane_cap=8)       # lane 0 full at cap
        p, ids, got, stt = pool_get_n(p, 0, 9, 0)
        taken = [int(i) for i in ids if int(i) >= 0]
        assert len(taken) == len(set(taken)) == int(got)
        # conservation: nothing duplicated, nothing lost
        assert int(free_count(p)) == 16 - int(got)
        remaining = {int(x) for x in np.asarray(p.slots).ravel() if x >= 0}
        assert remaining | set(taken) == set(range(16))
        assert remaining & set(taken) == set()


# ---------------------------------------------------------------------------
# Burst posting: doorbells, FIFO across splits, OFF batches
# ---------------------------------------------------------------------------

def _drain_tags(cq):
    tags = []
    while True:
        stt = cq.pop()
        if stt.is_retry():
            return tags
        tags.append(stt.tag)


class TestPostMany:
    def test_inject_burst_statuses_and_single_doorbell(self):
        cl = LocalCluster(2, CommConfig(inject_max_bytes=64))
        r0, r1 = cl[0], cl[1]
        cq = r1.alloc_cq()
        rc = r1.register_rcomp(cq)
        base_pushes = cl.fabric.pushes
        sts = r0.post_many([CommDesc(CommKind.AM, 1, np.zeros(8, np.uint8),
                                     tag=i, remote_comp=rc)
                            for i in range(16)])
        assert all(s.code == ErrorCode.DONE_INLINE for s in sts)
        assert cl.fabric.pushes == base_pushes + 16
        assert r0.engine.burst_posts == 1
        cl.quiesce()
        assert _drain_tags(cq) == list(range(16))

    def test_bufcopy_burst_amortizes_pool_locks(self):
        cfg = CommConfig(inject_max_bytes=1, packets_per_lane=64)
        cl = LocalCluster(2, cfg)
        r0, r1 = cl[0], cl[1]
        cq = r1.alloc_cq()
        rc = r1.register_rcomp(cq)
        pool = r0.packet_pool
        base = sum(lk.acquisitions for lk in pool.locks)
        for _ in range(4):                        # 4 doorbells of 16
            r0.post_many([CommDesc(CommKind.AM, 1, np.zeros(8, np.uint8),
                                   remote_comp=rc) for _ in range(16)])
            cl.quiesce()
        acqs = sum(lk.acquisitions for lk in pool.locks) - base
        # scalar plane: 2 per message = 128; burst plane: 1 get_n + a few
        # batched put_n per doorbell
        assert acqs <= 16, acqs
        assert len(_drain_tags(cq)) == 64
        assert pool.free_packets() == pool.n_packets

    def test_doorbell_split_preserves_fifo_per_peer(self):
        """Mid-burst RETRY_NOPACKET splits the doorbell; re-posting the
        failed suffix must still deliver every peer's tags in post order
        (by_peer stripe: one stream per peer)."""
        cfg = CommConfig(inject_max_bytes=1, packets_per_lane=6,
                         n_channels=2)
        cl = LocalCluster(3, cfg)
        eps = cl.alloc_endpoint(n_devices=2, stripe="by_peer",
                                progress="shared")
        cqs = {r: cl[r].alloc_cq() for r in (1, 2)}
        rcs = {r: cl[r].register_rcomp(cqs[r]) for r in (1, 2)}
        # interleave 10 tagged messages per peer, bursts of 8, tiny pool
        # (6 packets/lane) so every doorbell splits mid-burst
        pending = [CommDesc(CommKind.AM, peer, np.zeros(8, np.uint8),
                            tag=t, remote_comp=rcs[peer])
                   for t in range(10) for peer in (1, 2)]
        sent_guard = 0
        while pending:
            sts = eps[0].post_many(pending[:8])
            accepted = sum(1 for s in sts if not s.is_retry())
            # prefix-accept: the statuses must never accept past a retry
            seen_retry = False
            for s in sts:
                if s.is_retry():
                    seen_retry = True
                else:
                    assert not seen_retry, "doorbell accepted past a retry"
            pending = pending[accepted:]
            cl.quiesce()
            sent_guard += 1
            assert sent_guard < 200, "burst posting made no progress"
        assert _drain_tags(cqs[1]) == list(range(10))
        assert _drain_tags(cqs[2]) == list(range(10))

    def test_round_robin_burst_rides_one_stream_and_rotates(self):
        cl = LocalCluster(2, CommConfig(inject_max_bytes=64, n_channels=4))
        eps = cl.alloc_endpoint(n_devices=4, stripe="round_robin",
                                progress="dedicated")
        cq = cl[1].alloc_cq()
        rc = cl[1].register_rcomp(cq)
        for burst in range(8):
            eps[0].post_am_many(1, [np.zeros(8, np.uint8)] * 4, rc,
                                tags=[burst * 4 + i for i in range(4)])
        # each doorbell landed whole on one device; bursts rotated
        assert [d.pushes for d in eps[0].devices] == [8, 8, 8, 8]
        cl.quiesce()
        # per-stream FIFO: receiver tag order within a stream == post order
        tags = _drain_tags(cq)
        assert sorted(tags) == list(range(32))
        per_burst = [tags[i:i + 4] for i in range(0, 32, 4)]
        assert all(b == sorted(b) for b in per_burst)

    def test_zerocopy_op_cuts_run_but_keeps_order(self):
        cfg = CommConfig(inject_max_bytes=8, bufcopy_max_bytes=64)
        cl = LocalCluster(2, cfg)
        r0, r1 = cl[0], cl[1]
        sync = r1.alloc_sync(expected=3)
        bufs = [np.zeros(128, np.uint8), np.zeros(8, np.uint8),
                np.zeros(8, np.uint8)]
        for i, b in enumerate(bufs):
            post_recv_x(r1, 0, b, None, i, sync)()
        sts = r0.post_many([
            CommDesc(CommKind.SEND, 1, np.full(8, 1, np.uint8), tag=1),
            CommDesc(CommKind.SEND, 1, np.full(128, 9, np.uint8), tag=0),
            CommDesc(CommKind.SEND, 1, np.full(8, 2, np.uint8), tag=2),
        ])
        assert not any(s.is_retry() for s in sts)
        cl.quiesce()
        ok, _ = sync.test()
        assert ok
        assert bufs[0][0] == 9 and bufs[1][0] == 1 and bufs[2][0] == 2

    def test_off_batch_spelling(self):
        cl = LocalCluster(2, CommConfig(inject_max_bytes=64))
        r0, r1 = cl[0], cl[1]
        cq = r1.alloc_cq()
        rc = r1.register_rcomp(cq)
        b = post_am_x(r0, 1, np.zeros(8, np.uint8), None, None,
                      rc).tag(0).batch()
        assert isinstance(b, PostBatch) and len(b) == 1
        post_am_x(r0, 1, np.zeros(8, np.uint8), None, None,
                  rc).tag(1).batch(b)
        sts = b.flush()
        assert len(sts) == 2 and len(b) == 0      # reusable after flush
        cl.quiesce()
        assert _drain_tags(cq) == [0, 1]

    def test_post_batch_rejects_non_post_builders(self):
        from repro.core import progress_x
        cl = LocalCluster(1)
        with pytest.raises(Exception):
            progress_x(cl[0]).batch().flush()

    def test_post_many_endpoint_of_other_rank_raises(self):
        cl = LocalCluster(2, CommConfig(inject_max_bytes=64))
        eps = cl.alloc_endpoint(n_devices=1)
        with pytest.raises(Exception):
            post_many(cl[0], [CommDesc(CommKind.SEND, 1,
                                       np.zeros(4, np.uint8))],
                      endpoint=eps[1])


# ---------------------------------------------------------------------------
# Matching: lock-free probe-before-lock fast path (satellite hypothesis)
# ---------------------------------------------------------------------------

class TestMatchingFastPath:
    def test_fast_path_skips_bucket_lock(self):
        eng = HostMatchingEngine()
        key = make_key(0, 7)
        eng.insert(key, MatchKind.RECV, ("recv", None, None, None))
        lock = eng._lock_of(key)
        base = lock.acquisitions
        assert eng.match_now(key, MatchKind.SEND) is not None
        assert lock.acquisitions == base          # no lock taken
        assert eng.fast_matches == 1

    def test_fast_path_miss_returns_none_and_stores_nothing(self):
        eng = HostMatchingEngine()
        assert eng.match_now(make_key(0, 1), MatchKind.SEND) is None
        assert eng.pending() == 0

    @settings(max_examples=15, deadline=None)
    @given(st.integers(1, 24), st.integers(0, 2 ** 31 - 1))
    def test_concurrent_recv_vs_deliver_never_double_or_drop(
            self, n_msgs, seed):
        """Posted recvs race eager deliveries on the same key: every
        delivery matches at most one recv, every recv is consumed at most
        once, and nothing is lost — matched + leftover always adds up."""
        rng = np.random.default_rng(seed)
        eng = HostMatchingEngine()
        key = make_key(0, 3)
        deliverer_got = []            # recvs consumed by deliveries
        receiver_got = []             # stored sends consumed by post_recv
        barrier = threading.Barrier(2)

        def receiver():
            barrier.wait()
            for i in range(n_msgs):
                if rng.integers(2):
                    time.sleep(0)
                m = eng.insert(key, MatchKind.RECV, ("recv", i))
                if m is not None:
                    receiver_got.append(m)

        def deliverer():
            barrier.wait()
            for j in range(n_msgs):
                # the engine's delivery discipline: lock-free probe first,
                # locked insert fallback
                m = eng.match_now(key, MatchKind.SEND)
                if m is None:
                    m = eng.insert(key, MatchKind.SEND, ("eager", j))
                if m is not None:
                    deliverer_got.append(m)

        ts = [threading.Thread(target=receiver),
              threading.Thread(target=deliverer)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in ts)
        # drain leftovers single-threaded
        leftover_recvs, leftover_sends = [], []
        while True:
            m = eng.match_now(key, MatchKind.SEND)
            if m is None:
                break
            leftover_recvs.append(m)
        while True:
            m = eng.match_now(key, MatchKind.RECV)
            if m is None:
                break
            leftover_sends.append(m)
        assert eng.pending() == 0
        assert all(m[0] == "recv" for m in deliverer_got + leftover_recvs)
        assert all(m[0] == "eager" for m in receiver_got + leftover_sends)
        # never double-matched: every recv / send consumed exactly once
        recv_ids = [m[1] for m in deliverer_got + leftover_recvs]
        send_ids = [m[1] for m in receiver_got + leftover_sends]
        assert sorted(set(recv_ids)) == sorted(recv_ids)
        assert sorted(set(send_ids)) == sorted(send_ids)
        # never dropped: every recv and every send is accounted for
        assert (len(deliverer_got) + len(receiver_got)
                + len(leftover_recvs) == n_msgs)
        assert (len(deliverer_got) + len(receiver_got)
                + len(leftover_sends) == n_msgs)


# ---------------------------------------------------------------------------
# signal_many: prefix-accept + backlog redelivery order
# ---------------------------------------------------------------------------

class TestSignalMany:
    def test_cq_signal_many_prefix_accepts(self):
        cq = CompletionQueue(capacity=3)
        sts = cq.signal_many([done(tag=i) for i in range(5)])
        assert [s.is_done() for s in sts] == [True] * 3 + [False] * 2
        assert sts[3].code == ErrorCode.RETRY_QUEUE_FULL
        assert [cq.pop().tag for _ in range(3)] == [0, 1, 2]

    def test_tscq_signal_many_prefix_accepts(self):
        cq = ThreadSafeCompletionQueue(capacity=2)
        sts = cq.signal_many([done(tag=i) for i in range(4)])
        assert [s.is_done() for s in sts] == [True, True, False, False]
        assert cq.pop().tag == 0 and cq.pop().tag == 1

    def test_mixed_drain_keeps_per_comp_wire_order(self):
        """Regression: a drain holding an eager AM then a PUT-with-signal
        to the SAME comp must deliver in wire order — the eager signal
        batch flushes before any immediate rendezvous/RMA signal."""
        cl = LocalCluster(2, CommConfig(inject_max_bytes=64))
        r0, r1 = cl[0], cl[1]
        cq = r1.alloc_cq()
        rc = r1.register_rcomp(cq)
        target = np.zeros(8, np.uint8)
        region = r1.register_memory(target)
        from repro.core import post_put_x
        post_am_x(r0, 1, np.zeros(8, np.uint8), None, None, rc).tag(1)()
        post_put_x(r0, 1, np.full(8, 5, np.uint8), (region.rid, 0), 8,
                   None, rc).tag(2)()
        # both messages sit in one stream; a single pass drains both
        r1.progress(r1.default_device)
        cl.quiesce()
        tags = _drain_tags(cq)
        assert tags == [1, 2], tags

    def test_engine_parks_rejected_burst_in_order(self):
        """A full CQ rejects the burst's tail; the backlog must redeliver
        it in order once the client drains."""
        cl = LocalCluster(2, CommConfig(inject_max_bytes=64))
        r0, r1 = cl[0], cl[1]
        cq = r1.alloc_cq(capacity=4)
        rc = r1.register_rcomp(cq)
        r0.post_many([CommDesc(CommKind.AM, 1, np.zeros(8, np.uint8),
                               tag=i, remote_comp=rc) for i in range(10)])
        tags = []
        guard = 0
        while len(tags) < 10:
            cl.progress_all()
            tags.extend(_drain_tags(cq))
            guard += 1
            assert guard < 100
        assert tags == list(range(10))


# ---------------------------------------------------------------------------
# TSCQ liveness under burst signaling (satellite bugfix)
# ---------------------------------------------------------------------------

class TestTscqSpinBound:
    def test_wait_yields_against_mid_ticket_producer(self):
        """A producer that claimed a ticket but has not published makes
        len() > 0 while pop() fails; wait() must bounded-spin then yield
        (not busy-spin) until the slow producer publishes."""
        cq = ThreadSafeCompletionQueue()
        q = cq._q
        # simulate the descheduled producer: claim ticket 0, do NOT publish
        assert q._tail.compare_exchange(0, 1)
        assert len(cq) == 1                       # looks non-empty
        assert cq.pop().is_retry()                # but nothing published
        result = []

        def consumer():
            result.append(cq.wait(progress=None))

        t = threading.Thread(target=consumer, daemon=True)
        t.start()
        time.sleep(0.08)                          # consumer is in wait()
        assert t.is_alive()
        slot = q._slots[0]                        # producer finally publishes
        slot.data = done(tag=42)
        slot.seq = 1
        t.join(timeout=10)
        assert not t.is_alive()
        assert result and result[0].tag == 42
        # the spin bound engaged: the popper yielded instead of pegging
        assert cq.pop_yields > 0

    def test_wait_with_progress_driver_still_completes(self):
        cq = ThreadSafeCompletionQueue()
        cq.signal(done(tag=1))
        assert cq.wait(progress=lambda: None).tag == 1


# ---------------------------------------------------------------------------
# Burst progress: one try-lock acquisition drains a bounded burst
# ---------------------------------------------------------------------------

class TestBurstProgress:
    def test_bounded_drain_leaves_remainder(self):
        # scalar data plane: max_msgs bounds delivered completions 1:1
        cl = LocalCluster(2, CommConfig(inject_max_bytes=64),
                          attrs={"doorbell_fused": False})
        r0, r1 = cl[0], cl[1]
        cq = r1.alloc_cq()
        rc = r1.register_rcomp(cq)
        r0.post_many([CommDesc(CommKind.AM, 1, np.zeros(8, np.uint8),
                               tag=i, remote_comp=rc) for i in range(10)])
        dev = r1.default_device
        r1.engine.progress(dev, max_msgs=4)
        assert len(cq) == 4
        r1.engine.progress(dev, max_msgs=4)
        assert len(cq) == 8
        cl.quiesce()
        assert _drain_tags(cq) == list(range(10))

    def test_bounded_drain_counts_packed_doorbell_once(self):
        # fused data plane: the whole doorbell is ONE wire message, so a
        # drain limit admits all of its rows in one pass (DESIGN.md §13)
        cl = LocalCluster(2, CommConfig(inject_max_bytes=64),
                          attrs={"doorbell_fused": True})
        r0, r1 = cl[0], cl[1]
        cq = r1.alloc_cq()
        rc = r1.register_rcomp(cq)
        r0.post_many([CommDesc(CommKind.AM, 1, np.zeros(8, np.uint8),
                               tag=i, remote_comp=rc) for i in range(10)])
        r1.engine.progress(r1.default_device, max_msgs=4)
        assert len(cq) == 10
        cl.quiesce()
        assert _drain_tags(cq) == list(range(10))

    def test_worker_pool_burst_knob(self):
        from repro.core import ProgressWorkerPool, resolve_one
        cl = LocalCluster(1)
        pool = ProgressWorkerPool.for_runtime(cl[0], n_workers=1)
        # the default resolves through the attribute chain (library
        # default 64, REPRO_ATTR_WORKER_BURST honored)
        assert pool.burst == resolve_one("worker_burst")
        assert pool.counters()["burst"] == pool.burst
        explicit = ProgressWorkerPool.for_runtime(cl[0], n_workers=1,
                                                  burst=16)
        assert explicit.burst == 16
        with pytest.raises(Exception):
            ProgressWorkerPool([(cl[0].engine, cl[0].default_device)],
                               burst=-1)
