"""Fused doorbell data plane (DESIGN.md §13).

Covers the PR's tentpole end-to-end: the packed stage-copy
(``pack_payloads`` / the Pallas doorbell kernel), the single-descriptor
wire path (``push_packed`` / :class:`PackedBurst`), burst matching
(``match_now_n`` / ``match_now_burst`` / functional ``probe_batch``),
the fused allocate-and-stage (``pool_get_copy_n``), the ``wire_bf16``
compression attribute, and — the load-bearing property — byte- and
status-equivalence between the fused and the PR-4 scalar data planes.
"""
import sys

import numpy as np
import jax.numpy as jnp
import ml_dtypes
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (CommConfig, CommDesc, CommKind, HostMatchingEngine,
                        LocalCluster, MatchKind, MatchingPolicy, PackedBurst,
                        init_buffers, init_pool, init_table, insert_batch,
                        make_key, pack_payloads, pool_get_copy_n, post_recv,
                        probe, probe_batch)
from repro.core.progress.fabric import (Fabric, WireKind, WireMsg,
                                        payloads_to_bytes)
from repro.core.status import ErrorCode


# ---------------------------------------------------------------------------
# pack_payloads / payloads_to_bytes staging fast paths
# ---------------------------------------------------------------------------

class TestPackPayloads:
    def test_same_object_broadcast(self):
        p = np.arange(6, dtype=np.float32)
        data, sizes, wd = pack_payloads([p] * 5)
        assert data.shape == (5, 24) and wd is None
        assert data.strides[0] == 0                 # broadcast, no copies
        assert list(sizes) == [24] * 5
        assert np.array_equal(data[3], p.view(np.uint8))

    def test_uniform_stack(self):
        bufs = [np.full(4, i, np.int32) for i in range(6)]
        data, sizes, wd = pack_payloads(bufs)
        assert data.shape == (6, 16) and wd is None
        for i, b in enumerate(bufs):
            assert np.array_equal(data[i], b.view(np.uint8))

    def test_ragged_zero_padded(self):
        bufs = [np.arange(3, dtype=np.uint8), np.arange(7, dtype=np.uint8)]
        data, sizes, wd = pack_payloads(bufs)
        assert data.shape == (2, 7) and list(sizes) == [3, 7]
        assert np.array_equal(data[0, :3], bufs[0])
        assert not data[0, 3:].any()                # padding is zeros

    def test_bf16_applies_only_to_uniform_f32(self):
        f32 = [np.arange(4, dtype=np.float32)] * 3
        data, sizes, wd = pack_payloads(f32, wire_bf16=True)
        assert wd == "bf16" and data.shape == (3, 8)   # half the bytes
        assert list(sizes) == [16] * 3                 # delivered = f32
        ints = [np.arange(4, dtype=np.int32)] * 3
        data, _, wd = pack_payloads(ints, wire_bf16=True)
        assert wd is None and data.shape == (3, 16)    # bypass untouched

    def test_payloads_to_bytes_uniform_short_circuit(self):
        bufs = [np.full((2, 2), i, np.float64) for i in range(5)]
        fast = payloads_to_bytes(bufs)
        slow = [b.reshape(-1).view(np.uint8) for b in bufs]
        assert all(np.array_equal(f, s) for f, s in zip(fast, slow))

    def test_payloads_to_bytes_mixed_dtype_byte_exact(self):
        # regression for the stacked fast path: same nbytes, different
        # dtypes must still produce each payload's OWN bytes
        bufs = [np.arange(4, dtype=np.int32),
                np.arange(2, dtype=np.float64),
                np.frombuffer(b"0123456789abcdef", dtype=np.uint8).copy()]
        assert all(b.nbytes == 16 for b in bufs)
        out = payloads_to_bytes(bufs)
        for got, b in zip(out, bufs):
            assert np.array_equal(got, b.reshape(-1).view(np.uint8))


# ---------------------------------------------------------------------------
# PackedBurst + push_packed: weighted depth, prefix splits
# ---------------------------------------------------------------------------

def _packed_msg(k, row_bytes=8, dst=1, dev=0, tag=0):
    data = np.arange(k * row_bytes, dtype=np.uint8).reshape(k, row_bytes)
    burst = PackedBurst(data, np.full(k, row_bytes, np.int64),
                        [tag] * k, k)
    return WireMsg(WireKind.EAGER_PACKED_AM, src=0, dst=dst, tag=tag,
                   payload=burst, size=int(data.nbytes), rcomp=0,
                   device_index=dev)


class TestPushPacked:
    def test_packed_counts_rows_toward_depth(self):
        fab = Fabric(2, depth=10)
        assert fab.push_packed(_packed_msg(6)) == 6
        assert fab.stream_depth(1, 0) == 6
        assert fab.in_flight() == 6 and fab.pending_to(1) == 6
        # only 4 rows of room left: prefix-accept
        assert fab.push_packed(_packed_msg(6)) == 4
        assert fab.stream_depth(1, 0) == 10
        assert fab.push_packed(_packed_msg(3)) == 0    # full

    def test_prefix_split_slices_rows(self):
        fab = Fabric(2, depth=4)
        msg = _packed_msg(7)
        assert fab.push_packed(msg) == 4
        out = fab.drain(1, 0)
        assert len(out) == 1
        pb = out[0].payload
        assert pb.count == 4
        assert np.array_equal(pb.data, msg.payload.data[:4])
        assert out[0].size == pb.data.nbytes

    def test_drain_releases_packed_weight(self):
        fab = Fabric(2, depth=8)
        fab.push_packed(_packed_msg(5))
        assert fab.stream_depth(1, 0) == 5
        assert len(fab.drain(1, 0)) == 1
        assert fab.stream_depth(1, 0) == 0 and fab.in_flight() == 0
        # room is fully recycled afterwards
        assert fab.push_packed(_packed_msg(8)) == 8

    def test_scalar_and_packed_share_the_bound(self):
        fab = Fabric(2, depth=6)
        assert fab.try_push(WireMsg(WireKind.EAGER_AM, src=0, dst=1,
                                    payload=np.zeros(1, np.uint8), size=1,
                                    rcomp=0))
        assert fab.push_packed(_packed_msg(9)) == 5

    def test_delivered_payloads_bf16_roundtrip(self):
        f32 = np.linspace(-3, 3, 8, dtype=np.float32).reshape(2, 4)
        data, sizes, wd = pack_payloads(list(f32), wire_bf16=True)
        burst = PackedBurst(data, sizes, [0, 0], 2, wd)
        outs = burst.delivered_payloads()
        for got, want in zip(outs, f32):
            dec = got.view(np.float32)
            assert dec.dtype == np.float32 and got.nbytes == 16
            np.testing.assert_allclose(dec, want, atol=2e-2, rtol=2e-2)


# ---------------------------------------------------------------------------
# burst matching: host engine + functional probes
# ---------------------------------------------------------------------------

class TestBurstMatching:
    def test_match_now_n_pops_fifo(self):
        m = HostMatchingEngine(n_buckets=64, n_locks=4)
        key = make_key(1, 7, MatchingPolicy.RANK_TAG)
        for i in range(3):
            m.insert(key, MatchKind.RECV, ("recv", i))
        got = m.match_now_n(key, MatchKind.SEND, 5)
        assert [v[1] for v in got] == [0, 1, 2]      # FIFO, short is fine
        assert m.match_now_n(key, MatchKind.SEND, 1) == []

    def test_match_now_burst_groups_duplicate_keys(self):
        m = HostMatchingEngine(n_buckets=64, n_locks=4)
        ka = make_key(1, 1, MatchingPolicy.RANK_TAG)
        kb = make_key(1, 2, MatchingPolicy.RANK_TAG)
        m.insert(ka, MatchKind.RECV, "a0")
        m.insert(ka, MatchKind.RECV, "a1")
        m.insert(kb, MatchKind.RECV, "b0")
        out = m.match_now_burst([ka, kb, ka, ka], MatchKind.SEND)
        assert out == ["a0", "b0", "a1", None]       # aligned, FIFO per key

    def test_functional_probe_batch_matches_scan(self):
        table = init_table(n_buckets=32, bucket_cap=4)
        keys = jnp.asarray([5, 9, 5, 40], jnp.int32)
        vals = jnp.asarray([50, 90, 51, 400], jnp.int32)
        table, _, status = insert_batch(
            table, keys, jnp.full(4, int(MatchKind.RECV), jnp.int32), vals)
        assert list(np.asarray(status)) == [0, 0, 0, 0]   # all stored
        q = jnp.asarray([5, 5, 9, 7, 5], jnp.int32)
        table, out_vals, hits = probe_batch(table, q, int(MatchKind.SEND))
        assert list(np.asarray(hits)) == [1, 1, 1, 0, 0]
        assert list(np.asarray(out_vals)[:3]) == [50, 51, 90]  # FIFO dups
        # the popped entries are really gone
        table, _, hit = probe(table, jnp.int32(9), int(MatchKind.SEND))
        assert not bool(hit)


# ---------------------------------------------------------------------------
# pool_get_copy_n: fused allocate-and-stage
# ---------------------------------------------------------------------------

class TestPoolGetCopyN:
    def test_full_burst_writes_all_rows(self):
        pool = init_pool(n_lanes=1, packets_per_lane=8)
        buf = init_buffers(8, 16)
        payload = jnp.arange(4 * 10, dtype=jnp.uint8).reshape(4, 10)
        pool, buf, ids, got, status = pool_get_copy_n(pool, buf, 0,
                                                      payload, 0)
        assert int(got) == 4 and int(status) == 0
        for i, pid in enumerate(np.asarray(ids)):
            row = np.asarray(buf[int(pid)])
            assert np.array_equal(row[:10], np.asarray(payload[i]))
            assert not row[10:].any()                # packet-width padding

    def test_short_grab_writes_prefix_only(self):
        pool = init_pool(n_lanes=1, packets_per_lane=2)
        buf = init_buffers(2, 8)
        payload = jnp.full((5, 8), 7, jnp.uint8)
        pool, buf, ids, got, status = pool_get_copy_n(pool, buf, 0,
                                                      payload, 0)
        assert int(got) == 2 and int(status) != 0
        ids = np.asarray(ids)
        assert (ids[2:] == -1).all()
        assert np.asarray(buf)[np.sort(ids[:2])].all()

    def test_oversize_row_rejected_statically(self):
        pool = init_pool(n_lanes=1, packets_per_lane=2)
        buf = init_buffers(2, 8)
        with pytest.raises(ValueError):
            pool_get_copy_n(pool, buf, 0, jnp.zeros((1, 9), jnp.uint8), 0)


# ---------------------------------------------------------------------------
# doorbell Pallas kernel vs jnp oracle
# ---------------------------------------------------------------------------

class TestDoorbellKernel:
    def test_stage_copy_matches_ref(self):
        from repro.kernels.doorbell import stage_copy, stage_copy_ref
        x = jnp.asarray(np.random.RandomState(0)
                        .randn(16, 5).astype(np.float32))
        for bf16 in (False, True):
            out = np.asarray(stage_copy(x, wire_bf16=bf16, interpret=True))
            ref = np.asarray(stage_copy_ref(x, wire_bf16=bf16))
            assert np.array_equal(out, ref)
        assert np.array_equal(
            np.asarray(stage_copy(x, interpret=True)).view(np.float32),
            np.asarray(x))

    def test_stage_copy_push_lands_in_packets(self):
        from repro.kernels.doorbell import stage_copy, stage_copy_push
        x = jnp.asarray(np.random.RandomState(1)
                        .randn(4, 3).astype(np.float32))
        pool = init_pool(n_lanes=1, packets_per_lane=8)
        buf = init_buffers(8, 32)
        pool, buf, ids, got, status = stage_copy_push(
            pool, buf, 0, x, 0, wire_bf16=True, interpret=True)
        assert int(got) == 4 and int(status) == 0
        want = np.asarray(stage_copy(x, wire_bf16=True, interpret=True))
        for i, pid in enumerate(np.asarray(ids)):
            assert np.array_equal(np.asarray(buf[int(pid)])[:6], want[i])


# ---------------------------------------------------------------------------
# wire_bf16 end-to-end round trip
# ---------------------------------------------------------------------------

def _pump(cl, eps, rounds=6):
    for _ in range(rounds):
        for ep in eps:
            ep.progress()


class TestWireBf16:
    def test_f32_roundtrip_within_tolerance(self):
        cl = LocalCluster(2, attrs={"eager_max_bytes": 64,
                                    "doorbell_fused": True,
                                    "wire_bf16": True})
        eps = cl.alloc_endpoint(n_devices=1, name="ep")
        cq = cl[1].alloc_cq()
        rc = cl[1].register_rcomp(cq)
        rng = np.random.RandomState(3)
        bufs = [rng.randn(4).astype(np.float32) for _ in range(8)]
        sts = eps[0].post_am_many(1, bufs, rc)
        assert all(s.is_done() for s in sts)
        _pump(cl, eps)
        got = []
        while True:
            s = cq.pop()
            if not s.is_done():
                break
            v = np.asarray(s.value).view(np.float32)
            assert v.nbytes == 16                    # f32 at delivery
            got.append(tuple(np.round(v, 1)))
        assert len(got) == 8
        want = sorted(tuple(np.round(b, 1)) for b in bufs)
        assert sorted(got) == want                   # lossy but close

    def test_non_float_bypass_byte_exact(self):
        cl = LocalCluster(2, attrs={"eager_max_bytes": 64,
                                    "doorbell_fused": True,
                                    "wire_bf16": True})
        eps = cl.alloc_endpoint(n_devices=1, name="ep")
        cq = cl[1].alloc_cq()
        rc = cl[1].register_rcomp(cq)
        bufs = [np.arange(i, i + 4, dtype=np.int32) for i in range(8)]
        eps[0].post_am_many(1, bufs, rc)
        _pump(cl, eps)
        got = set()
        while True:
            s = cq.pop()
            if not s.is_done():
                break
            got.add(tuple(np.asarray(s.value).view(np.int32)))
        assert got == {tuple(b) for b in bufs}       # untouched bytes


# ---------------------------------------------------------------------------
# the load-bearing property: fused == scalar data plane
# ---------------------------------------------------------------------------

def _cluster(fused, *, em=16, ppl=64, depth=1 << 16):
    # pool_lanes=1: segment-level steal attempts legitimately differ
    # between one packed get_n and K scalar gets, so single-lane pools
    # keep allocation order bit-identical for the comparison.
    # chaos_* zeroed at the runtime layer: this property compares exact
    # delivered bytes between two data planes, so env-injected faults
    # (the chaos CI leg) must not perturb either side
    return LocalCluster(2, attrs={"eager_max_bytes": em,
                                  "doorbell_fused": fused,
                                  "packets_per_lane": ppl,
                                  "pool_lanes": 1,
                                  "chaos_drop": 0.0, "chaos_dup": 0.0,
                                  "chaos_reorder": 0.0,
                                  "chaos_delay_p": 0.0},
                        fabric_depth=depth)


def _st_sig(sts):
    return [(s.kind, s.code) for s in sts]


def _drive_am(fused, sizes, tags, em, ppl, depth):
    cl = _cluster(fused, em=em, ppl=ppl, depth=depth)
    eps = cl.alloc_endpoint(n_devices=1, name="ep")
    cq = cl[1].alloc_cq()
    rc = cl[1].register_rcomp(cq)
    bufs = [np.arange(sz, dtype=np.uint8) + (3 * i) % 251
            for i, sz in enumerate(sizes)]
    sts = eps[0].post_am_many(1, bufs, rc, tags=list(tags))
    _pump(cl, eps)
    got = []
    while True:
        s = cq.pop()
        if not s.is_done():
            break
        got.append((s.tag, bytes(np.asarray(s.value))))
    return _st_sig(sts), sorted(got)


def _drive_send(fused, sizes, tags, recv_tags, em, ppl, depth):
    cl = _cluster(fused, em=em, ppl=ppl, depth=depth)
    eps = cl.alloc_endpoint(n_devices=1, name="ep")
    scq, dcq = cl[0].alloc_cq(), cl[1].alloc_cq()
    recvs = [np.zeros(max(sizes, default=1), np.uint8) for _ in recv_tags]
    for rb, t in zip(recvs, recv_tags):
        post_recv(cl[1], 0, rb, tag=t, local_comp=dcq)
    bufs = [np.arange(sz, dtype=np.uint8) + (5 * i) % 251
            for i, sz in enumerate(sizes)]
    sts = eps[0].post_send_many(1, bufs, tags=list(tags), local_comp=scq)
    _pump(cl, eps, rounds=8)
    ndone = 0
    while dcq.pop().is_done():
        ndone += 1
    nsrc = 0
    while scq.pop().is_done():
        nsrc += 1
    return (_st_sig(sts), ndone, nsrc,
            [bytes(rb) for rb in recvs])


class TestFusedScalarEquivalence:
    @settings(max_examples=12, deadline=None)
    @given(st.lists(st.tuples(st.integers(1, 32), st.integers(0, 2)),
                    min_size=1, max_size=20),
           st.integers(0, 2))
    def test_am_equivalence(self, ops, scenario):
        sizes = [s for s, _ in ops]
        tags = [t for _, t in ops]
        em, ppl, depth = [(16, 64, 1 << 16),   # plain mixed inject/bufcopy
                          (8, 4, 1 << 16),     # pool exhaustion splits
                          (16, 64, 3),         # fabric back-pressure splits
                          ][scenario]
        f_sts, f_got = _drive_am(True, sizes, tags, em, ppl, depth)
        s_sts, s_got = _drive_am(False, sizes, tags, em, ppl, depth)
        assert f_sts == s_sts                  # identical split points
        assert f_got == s_got                  # identical delivered bytes

    @settings(max_examples=10, deadline=None)
    @given(st.lists(st.tuples(st.integers(1, 24), st.integers(0, 1)),
                    min_size=1, max_size=12),
           st.lists(st.integers(0, 1), min_size=0, max_size=12))
    def test_send_equivalence(self, ops, recv_tags):
        # duplicate match keys on both sides; pre-posted recvs may
        # under- or over-cover the burst (unexpected-queue fallback)
        sizes = [s for s, _ in ops]
        tags = [t for _, t in ops]
        f = _drive_send(True, sizes, tags, recv_tags, 8, 64, 1 << 16)
        s = _drive_send(False, sizes, tags, recv_tags, 8, 64, 1 << 16)
        assert f == s


class TestFusedGating:
    def test_short_runs_ride_the_scalar_path(self):
        cl = _cluster(True)
        eps = cl.alloc_endpoint(n_devices=1, name="ep")
        cq = cl[1].alloc_cq()
        rc = cl[1].register_rcomp(cq)
        before = cl[0].fabric.pushes
        k = cl[0].fused_min_burst - 1
        eps[0].post_am_many(1, [np.zeros(4, np.uint8)] * k, rc)
        assert cl[0].fabric.pushes - before == k   # k scalar wire msgs

    def test_fused_run_is_one_descriptor(self):
        cl = _cluster(True)
        eps = cl.alloc_endpoint(n_devices=1, name="ep")
        cq = cl[1].alloc_cq()
        rc = cl[1].register_rcomp(cq)
        eps[0].post_am_many(1, [np.zeros(4, np.uint8)] * 8, rc)
        out = cl[0].fabric.drain(1, eps[0].devices[0].index)
        assert len(out) == 1
        assert out[0].kind == WireKind.EAGER_PACKED_AM
        assert out[0].payload.count == 8

    def test_attr_off_disables_fusion(self):
        cl = _cluster(False)
        eps = cl.alloc_endpoint(n_devices=1, name="ep")
        cq = cl[1].alloc_cq()
        rc = cl[1].register_rcomp(cq)
        eps[0].post_am_many(1, [np.zeros(4, np.uint8)] * 8, rc)
        out = cl[0].fabric.drain(1, eps[0].devices[0].index)
        assert len(out) == 8
        assert all(m.kind == WireKind.EAGER_AM for m in out)
