"""Pluggable transport backends (DESIGN.md §14): the wire codec, the shm
ring and socket transports, backend selection through the attr chain, and
cross-backend parity of the full protocol stack (eager / bufcopy /
rendezvous) — every backend must deliver byte-identical payloads."""
import os
import threading

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (AttrError, LocalCluster, PackedBurst, Transport,
                        backend_class, decode_msg, encode_msg,
                        make_transport, msg_weight, post_am, post_recv,
                        post_send)
from repro.core.matching import MatchingPolicy
from repro.core.transport.shm import ShmTransport
from repro.core.transport.sim import Fabric
from repro.core.transport.socket import SocketTransport
from repro.core.transport.wire import PACKED_KINDS, WireKind, WireMsg

SCALAR_KINDS = sorted(v for k, v in vars(WireKind).items()
                      if not k.startswith("_") and v not in PACKED_KINDS)


def _assert_msg_equal(a: WireMsg, b: WireMsg):
    assert a.kind == b.kind
    assert (a.src, a.dst, a.tag, a.size, a.op_id) == \
           (b.src, b.dst, b.tag, b.size, b.op_id)
    assert a.rcomp == b.rcomp
    assert a.matching_policy == b.matching_policy
    assert a.device_index == b.device_index
    assert a.remote_buf == (tuple(b.remote_buf)
                            if b.remote_buf is not None else None)
    if b.payload is None:
        assert a.payload is None
    elif isinstance(b.payload, tuple):
        assert a.payload == b.payload
    elif isinstance(b.payload, PackedBurst):
        got, want = a.payload, b.payload
        assert got.count == want.count
        assert got.tags == list(want.tags)
        assert got.wire_dtype == want.wire_dtype
        assert np.array_equal(got.sizes, want.sizes)
        for g, w in zip(got.delivered_payloads(),
                        want.delivered_payloads()):
            assert np.array_equal(g, w)
    else:
        assert np.array_equal(a.payload,
                              b.payload.reshape(-1).view(np.uint8))


# ---------------------------------------------------------------------------
# codec: stable binary round trip (satellite 1)
# ---------------------------------------------------------------------------

class TestCodecRoundtrip:
    @settings(max_examples=60, deadline=None)
    @given(st.sampled_from(SCALAR_KINDS),
           st.integers(0, 7), st.integers(0, 7),
           st.integers(0, 2**31 - 1),
           st.integers(-1, 2**31 - 1),
           st.integers(-1, 100),            # rcomp (-1 = None)
           st.sampled_from(list(MatchingPolicy)),
           st.integers(0, 5),
           st.integers(-1, 2),              # payload selector
           st.lists(st.integers(0, 255), min_size=0, max_size=64),
           st.booleans())
    def test_scalar_roundtrip(self, kind, src, dst, tag, op_id, rcomp,
                              policy, didx, pselect, body, with_rbuf):
        if pselect < 0:
            payload = None
        elif pselect == 0:
            payload = np.asarray(body, dtype=np.uint8)
        else:
            payload = tuple(body[:8])
        msg = WireMsg(kind, src, dst, tag=tag, payload=payload,
                      size=len(body), rcomp=None if rcomp < 0 else rcomp,
                      matching_policy=policy, op_id=op_id,
                      remote_buf=(tag % 5, op_id % 97) if with_rbuf
                      else None,
                      device_index=didx, ready_at=0.25)
        out, end = decode_msg(encode_msg(msg))
        assert end == len(encode_msg(msg))
        _assert_msg_equal(out, msg)
        assert out.ready_at == msg.ready_at

    @settings(max_examples=40, deadline=None)
    @given(st.integers(1, 6),               # rows
           st.integers(1, 24),              # max row bytes
           st.lists(st.integers(0, 2**31 - 1), min_size=6, max_size=6),
           st.booleans())                   # ragged?
    def test_packed_roundtrip(self, k, row_bytes, tags, ragged):
        rng = np.random.default_rng(k * 1000 + row_bytes)
        data = rng.integers(0, 256, (k, row_bytes), dtype=np.uint8)
        sizes = (rng.integers(0, row_bytes + 1, k).astype(np.int64)
                 if ragged else np.full(k, row_bytes, np.int64))
        burst = PackedBurst(data, sizes, [int(t) for t in tags[:k]], k)
        msg = WireMsg(WireKind.EAGER_PACKED_AM, 0, 1, payload=burst,
                      size=int(data.nbytes), rcomp=0)
        out, _ = decode_msg(encode_msg(msg))
        _assert_msg_equal(out, msg)
        assert msg_weight(out) == k

    @settings(max_examples=20, deadline=None)
    @given(st.integers(1, 5), st.integers(1, 8))
    def test_packed_bf16_roundtrip(self, k, n_floats):
        """bf16-compressed rows decode to the same delivered f32 bytes."""
        import ml_dtypes
        f32 = np.linspace(-3, 3, n_floats, dtype=np.float32)
        row = f32.astype(ml_dtypes.bfloat16).view(np.uint8)
        # broadcast stride-0 rows — the message-rate hot path's wire image
        data = np.broadcast_to(row, (k, row.size))
        burst = PackedBurst(data, np.full(k, f32.nbytes, np.int64),
                            list(range(k)), k, wire_dtype="bf16")
        msg = WireMsg(WireKind.EAGER_PACKED_SEND, 0, 1, payload=burst,
                      size=int(data.nbytes))
        out, _ = decode_msg(encode_msg(msg))
        assert out.payload.wire_dtype == "bf16"
        for got, want in zip(out.payload.delivered_payloads(),
                             burst.delivered_payloads()):
            assert np.array_equal(got, want)

    def test_rejects_foreign_frames(self):
        from repro.core.status import FatalError
        with pytest.raises(FatalError, match="magic"):
            decode_msg(b"\x00" * 128)

    def test_codec_against_sim_backend(self):
        """Standalone contract: a decoded message is indistinguishable
        from the original to the sim fabric (satellite requirement)."""
        fab = Fabric(2)
        originals = [
            WireMsg(WireKind.EAGER_AM, 0, 1, tag=i,
                    payload=np.full(8, i, np.uint8), size=8, rcomp=0)
            for i in range(4)
        ]
        for m in originals:
            decoded, _ = decode_msg(encode_msg(m))
            assert fab.try_push(decoded)
        out = fab.drain(1, 0)
        assert [m.tag for m in out] == [0, 1, 2, 3]
        for got, want in zip(out, originals):
            _assert_msg_equal(got, want)


# ---------------------------------------------------------------------------
# backend registry + attr-chain selection (satellite 6)
# ---------------------------------------------------------------------------

class TestBackendSelection:
    def test_registry_resolves_all_backends(self):
        assert backend_class("sim") is Fabric
        assert backend_class("shm") is ShmTransport
        assert backend_class("socket") is SocketTransport
        for name in ("sim", "shm", "socket"):
            assert issubclass(backend_class(name), Transport)

    def test_unknown_backend_raises_attr_error(self):
        with pytest.raises(AttrError, match="registered backends"):
            backend_class("infiniband")
        with pytest.raises(AttrError):
            make_transport("infiniband", 2)

    def test_invalid_backend_attr_rejected_at_alloc(self):
        with pytest.raises(AttrError):
            LocalCluster(2, attrs={"fabric_backend": "carrier_pigeon"})

    def test_env_layer_selects_backend(self, monkeypatch):
        monkeypatch.setenv("REPRO_ATTR_FABRIC_BACKEND", "shm")
        cl = LocalCluster(2)
        try:
            assert cl.fabric.backend == "shm"
            # the chaos CI leg wraps the backend in ChaosTransport; the
            # wrapper echoes .backend and attrs but the concrete class
            # lives one level down
            base = getattr(cl.fabric, "inner", cl.fabric)
            assert isinstance(base, ShmTransport)
            assert cl.fabric.get_attr("fabric_backend") == "shm"
            assert cl.fabric.attr_source("fabric_backend") == "env"
        finally:
            cl.close()

    def test_introspection_on_fabric(self):
        cl = LocalCluster(2, attrs={"fabric_backend": "shm",
                                    "shm_ring_bytes": 65536})
        try:
            fab = cl.fabric
            assert fab.get_attr("fabric_backend") == "shm"
            assert fab.get_attr("shm_ring_bytes") == 65536
            assert fab.attr_source("fabric_backend") == "runtime"
            assert fab.attr_source("fabric_depth") == "default"
            echoed = fab.attrs
            assert echoed["fabric_backend"] == "shm"
            assert echoed["shm_ring_bytes"] == 65536
            assert "in_flight" in echoed
        finally:
            cl.close()

    def test_default_backend_is_sim(self, monkeypatch):
        # CI runs the whole suite under REPRO_ATTR_FABRIC_BACKEND=shm (and
        # the chaos leg under REPRO_ATTR_CHAOS_*); this test is about the
        # *library* default, so strip the env layer entirely
        monkeypatch.delenv("REPRO_ATTR_FABRIC_BACKEND", raising=False)
        for var in ("REPRO_ATTR_CHAOS_DROP", "REPRO_ATTR_CHAOS_DUP",
                    "REPRO_ATTR_CHAOS_REORDER", "REPRO_ATTR_CHAOS_DELAY_P",
                    "REPRO_ATTR_CHAOS_SEED", "REPRO_ATTR_CHAOS_KILL_RANK"):
            monkeypatch.delenv(var, raising=False)
        cl = LocalCluster(2)
        assert isinstance(cl.fabric, Fabric)
        assert cl.fabric.get_attr("fabric_backend") == "sim"
        assert cl.fabric.attr_source("fabric_backend") == "default"


# ---------------------------------------------------------------------------
# shm transport mechanics
# ---------------------------------------------------------------------------

def _shm_pair(tmp_path, **kw):
    """Producer (rank 0) and consumer (rank 1) instances sharing one
    session — the two-process topology, in one test process."""
    session = str(tmp_path / "sess")
    a = ShmTransport(2, rank=0, session=session, **kw)
    b = ShmTransport(2, rank=1, session=session, **kw)
    return a, b


def _am(i=0, dst=1, dev=0, nbytes=8):
    return WireMsg(WireKind.EAGER_AM, 0, dst, tag=i,
                   payload=np.full(nbytes, i % 256, np.uint8),
                   size=nbytes, rcomp=0, device_index=dev)


class TestShmTransport:
    def test_cross_instance_fifo(self, tmp_path):
        a, b = _shm_pair(tmp_path)
        try:
            for i in range(10):
                assert a.try_push(_am(i))
            assert b.stream_depth(1, 0) == 10       # unlocked head peek
            out = b.drain(1, 0)
            assert [m.tag for m in out] == list(range(10))
            assert np.array_equal(out[3].payload,
                                  np.full(8, 3, np.uint8))
            assert b.stream_depth(1, 0) == 0
            assert not b.ready(1, 0)
        finally:
            a.close(); b.close()

    def test_depth_bound_prefix_accept(self, tmp_path):
        a, b = _shm_pair(tmp_path, depth=3)
        try:
            msgs = [_am(i) for i in range(5)]
            assert a.push_burst(msgs) == 3
            assert a.full_events == 1
            assert [m.tag for m in b.drain(1, 0)] == [0, 1, 2]
            assert a.push_burst(msgs[3:]) == 2      # room recycled
        finally:
            a.close(); b.close()

    def test_ring_byte_backpressure_and_wraparound(self, tmp_path):
        """A ring much smaller than the traffic forces wraparound and
        byte-level back-pressure; nothing is lost or reordered."""
        a, b = _shm_pair(tmp_path, ring_bytes=4096)
        try:
            sent = recvd = 0
            tags = []
            while sent < 300:
                if a.try_push(_am(sent, nbytes=100)):
                    sent += 1
                else:
                    got = b.drain(1, 0, limit=7)
                    assert got, "full ring but nothing drainable"
                    tags += [m.tag for m in got]
                    recvd += len(got)
            tags += [m.tag for m in b.drain(1, 0)]
            assert tags == list(range(300))
            assert a.in_flight() == 0 or b.in_flight() == 0
        finally:
            a.close(); b.close()

    def test_packed_doorbell_row_weighted(self, tmp_path):
        a, b = _shm_pair(tmp_path, depth=10)
        try:
            data = np.arange(48, dtype=np.uint8).reshape(6, 8)
            burst = PackedBurst(data, np.full(6, 8, np.int64),
                                list(range(6)), 6)
            msg = WireMsg(WireKind.EAGER_PACKED_AM, 0, 1, payload=burst,
                          size=48, rcomp=0)
            assert a.push_packed(msg) == 6
            assert b.stream_depth(1, 0) == 6        # rows, not records
            assert a.push_packed(msg) == 4          # prefix-accept split
            out = b.drain(1, 0)
            assert [m.payload.count for m in out] == [6, 4]
            assert np.array_equal(out[1].payload.data, data[:4])
            assert b.stream_depth(1, 0) == 0
        finally:
            a.close(); b.close()

    def test_oversized_payload_spills(self, tmp_path):
        a, b = _shm_pair(tmp_path, ring_bytes=8192)
        try:
            big = np.arange(32 * 1024, dtype=np.uint8) % 251
            msg = WireMsg(WireKind.RDMA_PAYLOAD, 0, 1, payload=big,
                          size=big.nbytes, op_id=7)
            assert a.try_push(msg)
            session = a._dir
            assert any(n.startswith("spill_")
                       for n in os.listdir(session))
            out = b.drain(1, 0)
            assert len(out) == 1
            assert np.array_equal(out[0].payload, big)
            # consumed spill files are reaped
            assert not any(n.startswith("spill_")
                           for n in os.listdir(session))
        finally:
            a.close(); b.close()

    def test_threaded_producers_one_consumer(self, tmp_path):
        """In-process multithreaded producers ride the per-ring lock;
        SPSC is per process, so this must be safe (solo-mode tier-1)."""
        t = ShmTransport(2, ring_bytes=1 << 16)
        try:
            per_thread, n_threads = 200, 4
            done = threading.Barrier(n_threads + 1)

            def producer(base):
                for i in range(per_thread):
                    while not t.try_push(_am(base + i, nbytes=16)):
                        pass
                done.wait()

            threads = [threading.Thread(target=producer,
                                        args=(k * per_thread,))
                       for k in range(n_threads)]
            got = []
            for th in threads:
                th.start()
            while len(got) < per_thread * n_threads:
                got += t.drain(1, 0, limit=32)
            done.wait(timeout=30)
            for th in threads:
                th.join(timeout=30)
            assert sorted(m.tag for m in got) == \
                list(range(per_thread * n_threads))
            assert t.in_flight() == 0
        finally:
            t.close()

    def test_solo_session_dir_reaped_on_close(self):
        t = ShmTransport(2)
        d = t._dir
        t.try_push(_am(0))
        assert os.path.isdir(d)
        t.close()
        assert not os.path.exists(d)


# ---------------------------------------------------------------------------
# socket transport mechanics
# ---------------------------------------------------------------------------

class TestSocketTransport:
    def test_cross_instance_fifo(self, tmp_path):
        session = str(tmp_path / "socksess")
        a = SocketTransport(2, rank=0, session=session)
        b = SocketTransport(2, rank=1, session=session)
        try:
            for i in range(20):
                assert a.try_push(_am(i))
            got = []
            deadline = 200
            while len(got) < 20 and deadline:
                got += b.drain(1, 0)
                deadline -= 1
            assert [m.tag for m in got] == list(range(20))
            assert b.stream_depth(1, 0) == 0
        finally:
            a.close(); b.close()

    def test_packed_and_tuple_payloads(self, tmp_path):
        session = str(tmp_path / "socksess2")
        a = SocketTransport(2, rank=0, session=session)
        b = SocketTransport(2, rank=1, session=session)
        try:
            data = np.arange(24, dtype=np.uint8).reshape(3, 8)
            burst = PackedBurst(data, np.full(3, 8, np.int64),
                                [9, 8, 7], 3)
            assert a.push_packed(WireMsg(
                WireKind.EAGER_PACKED_AM, 0, 1, payload=burst,
                size=24, rcomp=0)) == 3
            assert a.try_push(WireMsg(WireKind.CTS, 0, 1,
                                      payload=(5,), op_id=3))
            got = []
            for _ in range(200):
                got += b.drain(1, 0)
                if len(got) == 2:
                    break
            assert msg_weight(got[0]) == 3
            assert np.array_equal(got[0].payload.data, data)
            assert got[1].payload == (5,)
        finally:
            a.close(); b.close()


# ---------------------------------------------------------------------------
# cross-backend parity: the full protocol stack end to end
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["sim", "shm", "socket"])
class TestBackendParity:
    def test_eager_am_roundtrip(self, backend):
        cl = LocalCluster(2, attrs={"fabric_backend": backend})
        try:
            r0, r1 = cl[0], cl[1]
            cq = r1.alloc_cq()
            rc = r1.register_rcomp(cq)
            buf = np.arange(64, dtype=np.uint8)
            post_am(r0, 1, buf, remote_comp=rc)
            cl.quiesce()
            st = cq.pop()
            assert st.is_done()
            assert np.array_equal(
                np.asarray(st.value).view(np.uint8)[:64], buf)
        finally:
            cl.close()

    def test_send_recv_all_protocols(self, backend):
        """Eager, bufcopy, and zero-copy rendezvous payload sizes all
        deliver byte-identical data on every backend (rendezvous rides
        RTS/CTS tuple payloads + a multi-MB RDMA_PAYLOAD — the shm spill
        path)."""
        # eager_max lowered so 8000 B genuinely rides the bufcopy packets
        cl = LocalCluster(2, attrs={"fabric_backend": backend,
                                    "eager_max_bytes": 1024})
        try:
            r0, r1 = cl[0], cl[1]
            rng = np.random.default_rng(7)
            # inject-eager, bufcopy (≤ packet_bytes), zero-copy rendezvous
            for size in (64, 8000, 3 * 1024 * 1024):
                src = rng.integers(0, 256, size, dtype=np.uint8)
                dst = np.zeros(size, np.uint8)
                sync = r1.alloc_sync()
                post_recv(r1, 0, dst, size, tag=size % 997,
                          local_comp=sync)
                post_send(r0, 1, src, size, tag=size % 997)
                cl.quiesce()
                assert sync.test()[0]
                assert np.array_equal(dst, src), f"size {size}"
        finally:
            cl.close()


# ---------------------------------------------------------------------------
# drain-limit row weighting across backends
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["sim", "shm"])
def test_drain_limit_is_row_weighted(tmp_path, backend):
    """drain(limit=k) counts packed rows toward the cap on every backend
    that can see queued packed doorbells."""
    if backend == "sim":
        t = Fabric(2, depth=64)
    else:
        t = ShmTransport(2, depth=64)
    try:
        t.try_push(_am(0))
        data = np.zeros((5, 4), np.uint8)
        t.push_packed(WireMsg(WireKind.EAGER_PACKED_AM, 0, 1,
                              payload=PackedBurst(
                                  data, np.full(5, 4, np.int64),
                                  list(range(5)), 5),
                              size=20, rcomp=0))
        t.try_push(_am(1))
        assert t.stream_depth(1, 0) == 7
        out = t.drain(1, 0, limit=2)       # scalar + whole doorbell
        assert len(out) == 2 and msg_weight(out[1]) == 5
        assert t.stream_depth(1, 0) == 1   # depth dropped by the weight
        assert t.ready(1, 0)
        assert len(t.drain(1, 0)) == 1
        assert t.stream_depth(1, 0) == 0
    finally:
        t.close()
