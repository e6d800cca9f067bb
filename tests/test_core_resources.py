"""Unit + property tests for the LCI-X core resources (paper §4.1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (BacklogQueue, CompletionGraph, CompletionHandler,
                        CompletionQueue, ErrorCode, FatalError,
                        HostMatchingEngine, HostPacketPool, MatchKind,
                        MatchingPolicy, MPMCArray, Synchronizer, done,
                        encode_key, free_count, init_pool, init_ring,
                        init_table, insert, insert_batch, make_key,
                        pending_count, pool_get, pool_put, retry, ring_pop,
                        ring_push, ring_size)
from repro.core.post import CommKind, Direction, classify
from repro.core.off import off


# ---------------------------------------------------------------------------
# packet pool (paper §4.1.2)
# ---------------------------------------------------------------------------

class TestHostPacketPool:
    def test_local_get_put(self):
        pool = HostPacketPool(n_lanes=2, packets_per_lane=4)
        pid, stt = pool.get(0)
        assert stt.is_done() and 0 <= pid < 8
        assert pool.put(0, pid).is_done()
        assert pool.free_packets() == 8

    def test_steal_half(self):
        pool = HostPacketPool(n_lanes=2, packets_per_lane=4, seed=1)
        got = [pool.get(0)[0] for _ in range(4)]        # drain lane 0
        pid, stt = pool.get(0)                          # must steal from 1
        assert stt.is_done() and pid >= 4
        assert pool.steals == 1

    def test_exhaustion_retry(self):
        pool = HostPacketPool(n_lanes=1, packets_per_lane=2)
        pool.get(0)
        pool.get(0)
        pid, stt = pool.get(0)
        assert pid == -1 and stt.is_retry()
        assert stt.code == ErrorCode.RETRY_NOPACKET

    @given(st.lists(st.tuples(st.booleans(), st.integers(0, 3)),
                    max_size=200))
    @settings(max_examples=30, deadline=None)
    def test_conservation(self, ops):
        """No packet is ever lost or duplicated."""
        pool = HostPacketPool(n_lanes=4, packets_per_lane=4)
        held = []
        for is_get, lane in ops:
            if is_get:
                pid, stt = pool.get(lane)
                if stt.is_done():
                    held.append((lane, pid))
            elif held:
                lane0, pid = held.pop()
                pool.put(lane0, pid)
        assert pool.free_packets() + len(held) == 16
        live = [p for _, p in held]
        assert len(set(live)) == len(live)              # no duplicates


class TestFunctionalPool:
    def test_get_put_roundtrip(self):
        pool = init_pool(n_lanes=2, packets_per_lane=3)
        pool, pid, stt = jax.jit(pool_get)(pool, 0, 0)
        assert int(stt) == 0 and 0 <= int(pid) < 6
        pool, stt2 = jax.jit(pool_put)(pool, 0, pid)
        assert int(stt2) == 0
        assert int(free_count(pool)) == 6

    def test_steal_then_retry(self):
        pool = init_pool(n_lanes=2, packets_per_lane=2)
        for _ in range(2):                              # drain lane 0
            pool, pid, stt = pool_get(pool, 0, 0)
            assert int(stt) == 0
        pool, pid, stt = pool_get(pool, 0, 0)           # steals from lane 1
        assert int(stt) == 0 and int(pid) >= 2
        # drain the rest then expect retry
        pool, _, s1 = pool_get(pool, 0, 0)
        pool, _, s2 = pool_get(pool, 1, 0)
        pool, pid, s3 = pool_get(pool, 0, 0)
        assert int(s3) == 1 and int(pid) == -1

    @given(st.lists(st.tuples(st.booleans(), st.integers(0, 2)),
                    max_size=60))
    @settings(max_examples=20, deadline=None)
    def test_functional_conservation(self, ops):
        pool = init_pool(n_lanes=3, packets_per_lane=3)
        held = []
        for i, (is_get, lane) in enumerate(ops):
            if is_get:
                pool, pid, stt = pool_get(pool, lane, i)
                if int(stt) == 0:
                    held.append((lane, int(pid)))
            elif held:
                lane0, pid = held.pop()
                pool, _ = pool_put(pool, lane0, pid)
        assert int(free_count(pool)) + len(held) == 9
        live = [p for _, p in held]
        assert len(set(live)) == len(live)


# ---------------------------------------------------------------------------
# matching engine (paper §4.1.3 / §3.3.2)
# ---------------------------------------------------------------------------

class TestMatchingEngine:
    def test_send_then_recv(self):
        me = HostMatchingEngine()
        assert me.insert(make_key(0, 5), MatchKind.SEND, "payload") is None
        assert me.insert(make_key(0, 5), MatchKind.RECV, "buf") == "payload"
        assert me.pending() == 0

    def test_fifo_within_key(self):
        me = HostMatchingEngine()
        me.insert(make_key(1, 1), MatchKind.SEND, "a")
        me.insert(make_key(1, 1), MatchKind.SEND, "b")
        assert me.insert(make_key(1, 1), MatchKind.RECV, None) == "a"
        assert me.insert(make_key(1, 1), MatchKind.RECV, None) == "b"

    def test_wildcard_policies(self):
        k_send = make_key(3, 7, MatchingPolicy.RANK_ONLY)
        k_recv = make_key(3, 99, MatchingPolicy.RANK_ONLY)
        assert k_send == k_recv                         # tag wildcarded
        assert make_key(3, 7, MatchingPolicy.TAG_ONLY) == \
            make_key(55, 7, MatchingPolicy.TAG_ONLY)

    def test_custom_make_key(self):
        key = make_key(3, 7, custom=lambda r, t: r * 1000 + t)
        assert key == 3007

    @given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 3)),
                    max_size=80))
    @settings(max_examples=30, deadline=None)
    def test_match_conservation(self, pairs):
        """#matches == min(#sends, #recvs) per key; nothing vanishes."""
        me = HostMatchingEngine()
        from collections import Counter
        sends, recvs, matched = Counter(), Counter(), 0
        for i, (rank, tag) in enumerate(pairs):
            kind = MatchKind.SEND if i % 2 else MatchKind.RECV
            key = make_key(rank, tag)
            if me.insert(key, kind, i) is not None:
                matched += 1
            (sends if kind == MatchKind.SEND else recvs)[key] += 1
        expected = sum(min(sends[k], recvs[k])
                       for k in set(sends) | set(recvs))
        assert matched == expected
        assert me.pending() == sum(sends.values()) + sum(recvs.values()) \
            - 2 * matched

    def test_functional_engine_matches(self):
        table = init_table(n_buckets=64, bucket_cap=4)
        k = encode_key(2, 9)
        table, m1, s1 = insert(table, k, MatchKind.SEND, jnp.int32(42))
        assert int(m1) == -1 and int(s1) == 0
        table, m2, s2 = insert(table, k, MatchKind.RECV, jnp.int32(7))
        assert int(m2) == 42 and int(s2) == 1
        assert int(pending_count(table)) == 0

    def test_functional_bucket_overflow(self):
        table = init_table(n_buckets=1, bucket_cap=2)
        k1, k2, k3 = (encode_key(i, 0) for i in range(1, 4))
        table, _, s1 = insert(table, k1, MatchKind.SEND, jnp.int32(1))
        table, _, s2 = insert(table, k2, MatchKind.SEND, jnp.int32(2))
        table, _, s3 = insert(table, k3, MatchKind.SEND, jnp.int32(3))
        assert int(s3) == 2                              # bucket full: retry

    @given(st.lists(st.tuples(st.integers(0, 2), st.integers(0, 2),
                              st.booleans()), min_size=1, max_size=24))
    @settings(max_examples=20, deadline=None)
    def test_functional_vs_host(self, ops):
        """The in-graph engine agrees with the host engine on match counts."""
        table = init_table(n_buckets=128, bucket_cap=24)
        me = HostMatchingEngine()
        f_matches = h_matches = 0
        for i, (rank, tag, is_send) in enumerate(ops):
            kind = MatchKind.SEND if is_send else MatchKind.RECV
            table, m, s = insert(table, encode_key(rank, tag), kind,
                                 jnp.int32(i))
            f_matches += int(m) != -1
            h_matches += me.insert(make_key(rank, tag), kind, i) is not None
        assert f_matches == h_matches


# ---------------------------------------------------------------------------
# backlog / ring (paper §4.1.5)
# ---------------------------------------------------------------------------

class TestBacklogAndRing:
    def test_backlog_fifo_and_flag(self):
        bq = BacklogQueue()
        assert bq.empty_flag
        bq.push("a")
        bq.push("b")
        assert not bq.empty_flag
        assert bq.pop()[0] == "a"
        assert bq.pop()[0] == "b"
        assert bq.pop()[1].is_retry()

    def test_backlog_capacity(self):
        bq = BacklogQueue(capacity=1)
        assert bq.push(1).is_done()
        assert bq.push(2).is_retry()

    @given(st.lists(st.booleans(), max_size=100))
    @settings(max_examples=30, deadline=None)
    def test_ring_fifo_property(self, ops):
        ring = init_ring(cap=8, width=1)
        model = []
        pushed = 0
        for is_push in ops:
            if is_push:
                ring, stt = ring_push(ring, [pushed])
                if int(stt) == 0:
                    model.append(pushed)
                pushed += 1
            else:
                ring, rec, stt = ring_pop(ring)
                if int(stt) == 0:
                    assert model and int(rec[0]) == model.pop(0)
                else:
                    assert not model
        assert int(ring_size(ring)) == len(model)


# ---------------------------------------------------------------------------
# completion objects (paper §4.1.4) + MPMC array (§4.1.1)
# ---------------------------------------------------------------------------

class TestCompletion:
    def test_handler(self):
        seen = []
        h = CompletionHandler(seen.append)
        h.signal(done(1))
        assert len(seen) == 1 and h.signals == 1

    def test_queue_capacity_retry(self):
        cq = CompletionQueue(capacity=1)
        assert cq.signal(done(1)).is_done()
        assert cq.signal(done(2)).is_retry()
        assert cq.pop().is_done()
        assert cq.pop().is_retry()

    def test_synchronizer_multi_signal(self):
        sy = Synchronizer(expected=3)
        for i in range(3):
            assert not sy.ready
            sy.signal(done(i))
        assert sy.ready
        ok, payloads = sy.test()
        assert ok and len(payloads) == 3
        with pytest.raises(FatalError):
            sy.signal(done(9))

    def test_mpmc_array_growth(self):
        arr = MPMCArray(initial_cap=2)
        idxs = [arr.append(i) for i in range(20)]
        assert idxs == list(range(20))
        assert arr.resizes >= 3                          # doubled repeatedly
        assert arr[7] == 7
        with pytest.raises(FatalError):
            _ = arr[25]


# ---------------------------------------------------------------------------
# completion graph (paper §3.2.5)
# ---------------------------------------------------------------------------

class TestCompletionGraph:
    def test_partial_order_and_values(self):
        g = CompletionGraph()
        a = g.add_node(lambda: 2)
        b = g.add_node(lambda: 3)
        c = g.add_node(lambda x, y: x * y, deps=[a, b])
        d = g.add_node(lambda z: z + 1, deps=[c])
        vals = g.execute()
        assert vals[d] == 7
        g.assert_partial_order()
        assert g.critical_path_len() == 3

    def test_diamond_fires_once(self):
        fired = []
        g = CompletionGraph()
        a = g.add_node(lambda: fired.append("a") or 1)
        b = g.add_node(lambda x: fired.append("b") or x, deps=[a])
        c = g.add_node(lambda x: fired.append("c") or x, deps=[a])
        d = g.add_node(lambda x, y: fired.append("d") or x + y, deps=[b, c])
        g.execute()
        assert sorted(fired) == ["a", "b", "c", "d"]
        assert fired[0] == "a" and fired[-1] == "d"

    def test_bad_edges_rejected_at_insertion(self):
        g = CompletionGraph()
        a = g.add_node(lambda: 1)
        b = g.add_node(lambda x: x, deps=[a])
        with pytest.raises(FatalError):                  # backward => cycle
            g.add_edge(b, a)
        with pytest.raises(FatalError):                  # self-edge
            g.add_edge(a, a)
        with pytest.raises(FatalError):                  # duplicate of a dep
            g.add_edge(a, b)
        with pytest.raises(FatalError):                  # unknown node
            g.add_edge(a, 99)
        g.execute()                                      # graph still valid


# ---------------------------------------------------------------------------
# OFF idiom (§3.1) + Table 1 (§3.2.4)
# ---------------------------------------------------------------------------

class TestOffAndTable1:
    def test_off_any_order(self):
        calls = []

        @off
        def op(a, b, *, opt1=0, opt2="x"):
            calls.append((a, b, opt1, opt2))
            return len(calls)

        assert op.x(1, 2).opt2("y").opt1(5)() == 1
        assert op.x(1, 2).opt1(5).opt2("y")() == 2
        assert calls[0] == calls[1] == (1, 2, 5, "y")

    def test_off_unknown_option(self):
        @off
        def op(a, *, known=0):
            return a

        with pytest.raises(TypeError):
            op.x(1).unknown(2)

    @pytest.mark.parametrize("direction,rbuf,rcomp,expect", [
        (Direction.OUT, None, None, CommKind.SEND),
        (Direction.OUT, None, 1, CommKind.AM),
        (Direction.OUT, "buf", None, CommKind.PUT),
        (Direction.OUT, "buf", 1, CommKind.PUT_SIGNAL),
        (Direction.IN, None, None, CommKind.RECV),
        (Direction.IN, "buf", None, CommKind.GET),
    ])
    def test_table1_valid_rows(self, direction, rbuf, rcomp, expect):
        assert classify(direction, rbuf, rcomp) == expect

    def test_table1_invalid_row(self):
        with pytest.raises(FatalError):
            classify(Direction.IN, None, 1)

    def test_get_with_signal_unimplemented(self):
        with pytest.raises(NotImplementedError):
            classify(Direction.IN, "buf", 1)
