"""Host runtime end-to-end: the paper's iRPCLib example (Listing 2) as a
test, plus protocol, RMA, back-pressure, and a hypothesis delivery
property."""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (CommConfig, LocalCluster, MatchingPolicy, Protocol,
                        post_am_x, post_get_x, post_put_x, post_recv_x,
                        post_send_x, select_protocol)

CFG = CommConfig(inject_max_bytes=64, bufcopy_max_bytes=512)


@pytest.fixture()
def pair():
    cl = LocalCluster(2, CFG)
    return cl, cl[0], cl[1]


class TestProtocolSelection:
    def test_thresholds(self):
        assert select_protocol(64, CFG) == Protocol.INJECT
        assert select_protocol(65, CFG) == Protocol.BUFCOPY
        assert select_protocol(512, CFG) == Protocol.BUFCOPY
        assert select_protocol(513, CFG) == Protocol.ZEROCOPY


class TestActiveMessages:
    def test_inject_am_done_immediately(self, pair):
        cl, r0, r1 = pair
        cq = r1.alloc_cq()
        rc = r1.register_rcomp(cq)
        st = post_am_x(r0, 1, np.arange(8, dtype=np.uint8), None, None,
                       rc).tag(7)()
        assert st.is_done()
        cl.quiesce()
        msg = cq.pop()
        assert msg.is_done() and msg.tag == 7 and msg.rank == 0
        assert np.array_equal(msg.get_buffer(), np.arange(8, dtype=np.uint8))

    def test_bufcopy_am_signals_source(self, pair):
        cl, r0, r1 = pair
        freed = []
        h = r0.alloc_handler(freed.append)
        cq = r1.alloc_cq()
        rc = r1.register_rcomp(cq)
        st = post_am_x(r0, 1, np.arange(256, dtype=np.uint8), None, h, rc)()
        assert st.is_posted()
        cl.quiesce()
        assert len(freed) == 1 and cq.pop().is_done()
        # bufcopy returns the packet to the pool
        assert r0.packet_pool.free_packets() == r0.packet_pool.n_packets

    def test_zerocopy_am_rendezvous(self, pair):
        cl, r0, r1 = pair
        freed = []
        h = r0.alloc_handler(freed.append)
        cq = r1.alloc_cq()
        rc = r1.register_rcomp(cq)
        big = np.arange(4096, dtype=np.uint8).astype(np.uint8)
        st = post_am_x(r0, 1, big, None, h, rc)()
        assert st.is_posted()
        cl.quiesce()
        assert len(freed) == 1
        got = cq.pop()
        assert got.is_done() and np.array_equal(got.get_buffer(), big)
        assert r0.stats.handshakes >= 1                  # RTS/CTS happened


class TestSendRecv:
    def test_recv_first_then_send(self, pair):
        cl, r0, r1 = pair
        buf = np.zeros(16, np.uint8)
        assert post_recv_x(r1, 0, buf, 16, 3)().is_posted()
        assert post_send_x(r0, 1, np.full(16, 9, np.uint8), 16, 3)().is_done()
        cl.quiesce()
        assert np.all(buf == 9)

    def test_unexpected_send_matched_done(self, pair):
        cl, r0, r1 = pair
        post_send_x(r0, 1, np.full(16, 5, np.uint8), 16, 4)()
        cl.quiesce()
        buf = np.zeros(16, np.uint8)
        st = post_recv_x(r1, 0, buf, 16, 4)()
        assert st.is_done() and np.all(buf == 5)

    def test_zerocopy_send_recv(self, pair):
        cl, r0, r1 = pair
        data = np.arange(2048, dtype=np.uint8).astype(np.uint8)
        buf = np.zeros(2048, np.uint8)
        got = []
        h = r1.alloc_handler(got.append)
        post_recv_x(r1, 0, buf, 2048, 5).local_comp(h)()
        post_send_x(r0, 1, data, 2048, 5)()
        cl.quiesce()
        assert np.array_equal(buf, data) and len(got) == 1

    def test_rank_only_wildcard(self, pair):
        cl, r0, r1 = pair
        buf = np.zeros(8, np.uint8)
        post_recv_x(r1, 0, buf, 8, 0).matching_policy(
            MatchingPolicy.RANK_ONLY)()
        post_send_x(r0, 1, np.full(8, 3, np.uint8), 8, 99).matching_policy(
            MatchingPolicy.RANK_ONLY)()
        cl.quiesce()
        assert np.all(buf == 3)


class TestRMA:
    def test_put_and_get(self, pair):
        cl, r0, r1 = pair
        target = np.zeros(64, np.uint8)
        region = r1.register_memory(target)
        post_put_x(r0, 1, np.arange(64, dtype=np.uint8), (region.rid, 0),
                   64)()
        cl.quiesce()
        assert np.array_equal(target, np.arange(64, dtype=np.uint8))
        local = np.zeros(32, np.uint8)
        post_get_x(r0, 1, local, (region.rid, 16), 32)()
        cl.quiesce()
        assert np.array_equal(local, target[16:48])

    def test_put_with_signal(self, pair):
        cl, r0, r1 = pair
        target = np.zeros(8, np.uint8)
        region = r1.register_memory(target)
        cq = r1.alloc_cq()
        rc = r1.register_rcomp(cq)
        post_put_x(r0, 1, np.full(8, 1, np.uint8), (region.rid, 0),
                   8).remote_comp(rc)()
        cl.quiesce()
        assert cq.pop().is_done() and np.all(target == 1)

    def test_get_with_signal_not_implemented(self, pair):
        cl, r0, r1 = pair
        region = r1.register_memory(np.zeros(8, np.uint8))
        cq = r1.alloc_cq()
        rc = r1.register_rcomp(cq)
        with pytest.raises(NotImplementedError):
            post_get_x(r0, 1, np.zeros(8, np.uint8), (region.rid, 0),
                       8).remote_comp(rc)()


class TestBackPressure:
    def test_fabric_full_retry_then_backlog(self):
        cl = LocalCluster(2, CFG, fabric_depth=1)
        r0 = cl[0]
        assert post_send_x(r0, 1, np.zeros(8, np.uint8), 8, 0)().is_done()
        st = post_send_x(r0, 1, np.zeros(8, np.uint8), 8, 0)()
        assert st.is_retry()
        st = post_send_x(r0, 1, np.zeros(8, np.uint8), 8,
                         0).allow_retry(False)()
        assert st.is_posted() and st.code.name == "POSTED_BACKLOG"
        cl.quiesce()
        assert cl.fabric.pending_to(1) == 0

    def test_packet_exhaustion_retry(self):
        cfg = CommConfig(inject_max_bytes=4, bufcopy_max_bytes=512,
                         packets_per_lane=1, n_channels=1)
        cl = LocalCluster(2, cfg)
        r0 = cl[0]
        st1 = post_send_x(r0, 1, np.zeros(64, np.uint8), 64, 0)()
        assert st1.is_posted()
        st2 = post_send_x(r0, 1, np.zeros(64, np.uint8), 64, 1)()
        assert st2.is_retry() and st2.code.name == "RETRY_NOPACKET"
        cl.quiesce()                      # progress returns the packet
        st3 = post_send_x(r0, 1, np.zeros(64, np.uint8), 64, 2)()
        assert st3.is_posted()


class TestDedicatedDevices:
    def test_per_lane_devices_do_not_interfere(self):
        cl = LocalCluster(2, CFG)
        r0, r1 = cl[0], cl[1]
        devs0 = [r0.alloc_device() for _ in range(3)]
        devs1 = [r1.alloc_device() for _ in range(3)]
        cq = r1.alloc_cq()
        rc = r1.register_rcomp(cq)
        for i, d in enumerate(devs0):
            st = post_am_x(r0, 1, np.full(8, i, np.uint8), None, None,
                           rc).device(d)()
            assert st.is_done()
        cl.quiesce()
        seen = sorted(int(cq.pop().get_buffer()[0]) for _ in range(3))
        assert seen == [0, 1, 2]


@given(st.lists(st.tuples(st.integers(0, 3),      # tag
                          st.integers(1, 600)),   # size (all 3 protocols)
                min_size=1, max_size=40))
@settings(max_examples=25, deadline=None)
def test_delivery_property(msgs):
    """Every posted message is delivered exactly once, bytes intact,
    matched by (rank, tag), across all three protocols."""
    cl = LocalCluster(2, CFG)
    r0, r1 = cl[0], cl[1]
    cq = r1.alloc_cq()
    rc = r1.register_rcomp(cq)
    sent = []
    for i, (tag, size) in enumerate(msgs):
        payload = np.full(size, (i * 37 + tag) % 251, np.uint8)
        st = post_am_x(r0, 1, payload, None, None, rc).tag(tag)()
        while st.is_retry():
            cl.progress_all()
            st = post_am_x(r0, 1, payload, None, None, rc).tag(tag)()
        sent.append((tag, payload))
    cl.quiesce()
    got = []
    while True:
        msg = cq.pop()
        if msg.is_retry():
            break
        got.append((msg.tag, np.asarray(msg.get_buffer())))
    assert len(got) == len(sent)
    for (t1, p1), (t2, p2) in zip(sorted(sent, key=lambda x: (x[0], x[1].tobytes())),
                                  sorted(got, key=lambda x: (x[0], x[1].tobytes()))):
        assert t1 == t2 and np.array_equal(p1, p2[:len(p1)])
