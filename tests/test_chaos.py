"""Chaos plane end to end (DESIGN.md §16): fault injection, the
reliability protocol that survives it, post deadlines, rank death, codec
hardening, and the recovery pieces (straggler window, cfg-aware shrink,
mid-commit kill, spmd rank-kill smoke)."""
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import ErrorCode, LocalCluster, post_am, post_recv
from repro.core.transport.chaos import ChaosConfig, ChaosTransport
from repro.core.transport.codec import CodecError, decode_msg, encode_msg
from repro.core.transport.wire import WireKind, WireMsg

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
HELPERS = os.path.join(os.path.dirname(__file__), "helpers")

FAULTS = {"chaos_drop": 0.05, "chaos_dup": 0.05, "chaos_reorder": 0.05}


def _child_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    # children must see the library defaults, not this process's CI leg
    for k in list(env):
        if k.startswith("REPRO_ATTR_CHAOS"):
            del env[k]
    return env


def _deliver_all(cl, sender, receiver, n, *, size=32):
    """Post n tagged AMs sender->receiver, quiesce, return delivered tags
    in arrival order."""
    cq = receiver.alloc_cq()
    rc = receiver.register_rcomp(cq)
    for i in range(n):
        buf = np.full(size, i % 256, np.uint8)
        st = post_am(sender, receiver.rank, buf, remote_comp=rc, tag=i)
        while st.is_retry():
            sender.progress()
            st = post_am(sender, receiver.rank, buf, remote_comp=rc, tag=i)
    cl.quiesce()
    tags = []
    while True:
        st = cq.pop()
        if st.is_retry():
            return tags
        assert st.is_done()
        tags.append(st.tag)


# ---------------------------------------------------------------------------
# fault injection mechanics (the wrapper itself)
# ---------------------------------------------------------------------------

class TestChaosTransport:
    def test_inactive_config_skips_wrap(self):
        cl = LocalCluster(2, attrs={"chaos_drop": 0.0, "chaos_dup": 0.0,
                                    "chaos_reorder": 0.0,
                                    "chaos_delay_p": 0.0})
        try:
            assert not isinstance(cl.fabric, ChaosTransport)
        finally:
            cl.close()

    def test_active_config_wraps_and_counts(self):
        cl = LocalCluster(2, attrs={"chaos_drop": 0.2, "chaos_seed": 3,
                                    **{k: 0.0 for k in
                                       ("chaos_dup", "chaos_reorder")}})
        try:
            fab = cl.fabric
            assert isinstance(fab, ChaosTransport)
            tags = _deliver_all(cl, cl[0], cl[1], 100)
            assert tags == list(range(100))           # healed, in order
            assert fab.dropped.load() > 0             # faults really fired
            assert cl[0].rel is not None              # auto-armed rel
            assert cl[0].rel.counters()["retransmits"] > 0
        finally:
            cl.close()

    def test_same_seed_same_fault_sequence(self):
        """Determinism: the same seed over the same push/drain pattern
        makes identical fault decisions (the replay contract).  Unit
        level on purpose — end to end, retransmit *timing* feeds back
        into the drain pattern, which is exactly what replay fixes."""
        from repro.core.transport.sim import Fabric

        def run(seed):
            chaos = ChaosTransport(
                Fabric(2), ChaosConfig(seed=seed, drop=0.3, dup=0.2,
                                       reorder=0.2))
            survived = []
            for i in range(40):
                msg = WireMsg(WireKind.EAGER_AM, 0, 1, tag=i,
                              payload=np.zeros(4, np.uint8), size=4,
                              rcomp=0, device_index=0)
                msg.seq, msg.epoch = i, 0          # fault-eligible
                assert chaos.try_push(msg)
                survived += [m.tag for m in chaos.drain(1, 0)]
            survived += [m.tag for m in chaos.drain(1, 0)]
            return survived

        assert run(11) == run(11)
        assert run(11) != run(12)

    def test_acks_never_faulted(self):
        """Control traffic (seq < 0) passes untouched even at drop=1:
        only reliability-stamped messages are fault-eligible."""
        from repro.core.transport.sim import Fabric
        chaos = ChaosTransport(Fabric(2), ChaosConfig(seed=1, drop=1.0))
        ack = WireMsg(WireKind.ACK, 0, 1, payload=(5, 0), device_index=0)
        assert ack.seq < 0
        assert chaos.try_push(ack)
        out = chaos.drain(1, 0)
        assert len(out) == 1 and out[0].kind == WireKind.ACK
        assert chaos.dropped.load() == 0

    def test_dead_rank_swallows_traffic(self):
        from repro.core.transport.sim import Fabric
        chaos = ChaosTransport(Fabric(2), ChaosConfig(kill_rank=1))
        msg = WireMsg(WireKind.EAGER_AM, 0, 1,
                      payload=np.zeros(8, np.uint8), size=8, rcomp=0,
                      device_index=0)
        assert chaos.try_push(msg)        # accepted-and-dropped, no wedge
        assert chaos.drain(1, 0) == []
        assert chaos.dead_dropped.load() > 0


# ---------------------------------------------------------------------------
# the reliability property: no loss, no dup, per-stream FIFO
# ---------------------------------------------------------------------------

class TestReliabilityProperty:
    @settings(max_examples=8, deadline=None)
    @given(st.integers(0, 2 ** 31 - 1), st.integers(20, 80))
    def test_exactly_once_in_order_sim(self, seed, n):
        cl = LocalCluster(2, attrs={"chaos_seed": seed, **FAULTS})
        try:
            tags = _deliver_all(cl, cl[0], cl[1], n)
            assert tags == list(range(n))
        finally:
            cl.close()

    @pytest.mark.parametrize("backend", ["sim", "shm", "socket"])
    def test_exactly_once_in_order_backends(self, backend):
        """The acceptance bar: 5% drop = dup = reorder on every backend,
        zero lost and zero duplicated completions."""
        cl = LocalCluster(2, attrs={"fabric_backend": backend,
                                    "chaos_seed": 1234, **FAULTS})
        try:
            tags = _deliver_all(cl, cl[0], cl[1], 120)
            assert tags == list(range(120))
        finally:
            cl.close()

    def test_bufcopy_source_comps_exactly_once(self):
        """Dropped-then-retransmitted bufcopy sends still signal their
        local comp exactly once (ack-driven completion)."""
        cl = LocalCluster(2, attrs={"chaos_seed": 5, "eager_max_bytes": 0,
                                    **FAULTS})
        try:
            scq = cl[0].alloc_cq()
            cq = cl[1].alloc_cq()
            rc = cl[1].register_rcomp(cq)
            for i in range(60):
                st = post_am(cl[0], 1, np.full(32, i % 256, np.uint8),
                             local_comp=scq, remote_comp=rc, tag=i)
                while st.is_retry():
                    cl[0].progress()
                    st = post_am(cl[0], 1, np.full(32, i, np.uint8),
                                 local_comp=scq, remote_comp=rc, tag=i)
            cl.quiesce()
            sends = 0
            while scq.pop().is_done():
                sends += 1
            assert sends == 60
            assert not cl[0].pending_ops        # nothing leaked
        finally:
            cl.close()

    def test_fused_doorbell_under_chaos(self):
        """Packed doorbells allocate per-row seqs: a dropped burst heals
        row-exact, delivered once each and in order."""
        cl = LocalCluster(2, attrs={"chaos_seed": 77, "doorbell_fused": True,
                                    "eager_max_bytes": 64, **FAULTS})
        try:
            eps = cl.alloc_endpoint(n_devices=1, name="burst")
            cq = cl[1].alloc_cq()
            rc = cl[1].register_rcomp(cq)
            total = 0
            for base in range(0, 120, 8):
                bufs = [np.full(16, (base + j) % 256, np.uint8)
                        for j in range(8)]
                sts = eps[0].post_am_many(1, bufs, rc,
                                          tags=list(range(base, base + 8)))
                total += len(sts)
                cl.progress_all()
            cl.quiesce()
            tags = []
            while True:
                st = cq.pop()
                if st.is_retry():
                    break
                tags.append(st.tag)
            assert tags == list(range(total))
        finally:
            cl.close()


# ---------------------------------------------------------------------------
# deadlines and rank death
# ---------------------------------------------------------------------------

class TestDeadlinesAndDeath:
    def test_post_deadline_expires_err_timeout(self):
        """drop=1.0: nothing ever arrives, so the completion deadline
        fires ERR_TIMEOUT on the send's comp exactly once."""
        cl = LocalCluster(2, attrs={"chaos_drop": 1.0, "chaos_seed": 2,
                                    "eager_max_bytes": 0,
                                    "post_deadline_us": 20_000,
                                    "retry_limit": 1_000_000})
        try:
            scq = cl[0].alloc_cq()
            cq = cl[1].alloc_cq()
            rc = cl[1].register_rcomp(cq)
            st = post_am(cl[0], 1, np.zeros(32, np.uint8),
                         local_comp=scq, remote_comp=rc, tag=9)
            assert st.is_posted()
            deadline = time.monotonic() + 10.0
            got = None
            while got is None and time.monotonic() < deadline:
                cl.progress_all()
                s = scq.pop()
                if not s.is_retry():
                    got = s
            assert got is not None and got.is_err()
            assert got.code == ErrorCode.ERR_TIMEOUT
        finally:
            cl.close()

    def test_recv_deadline_expires(self):
        cl = LocalCluster(2, attrs={"reliability": "on",
                                    "post_deadline_us": 10_000})
        try:
            cq = cl[1].alloc_cq()
            buf = np.zeros(16, np.uint8)
            st = post_recv(cl[1], 0, buf, 16, 3, cq)
            assert st.is_posted()
            deadline = time.monotonic() + 10.0
            got = None
            while got is None and time.monotonic() < deadline:
                cl.progress_all()
                s = cq.pop()
                if not s.is_retry():
                    got = s
            assert got is not None and got.is_err()
            assert got.code == ErrorCode.ERR_TIMEOUT
        finally:
            cl.close()

    def test_post_to_dead_peer_fails_at_post_time(self):
        cl = LocalCluster(2, attrs={"reliability": "on"})
        try:
            cl[0].mark_peer_dead(1)
            st = post_am(cl[0], 1, np.zeros(8, np.uint8), remote_comp=0)
            assert st.is_err() and st.code == ErrorCode.ERR_PEER_DEAD
        finally:
            cl.close()

    def test_in_flight_fails_peer_dead_on_death(self):
        """Posts outstanding when the peer dies complete ERR_PEER_DEAD on
        the next sweep — no hang, nothing leaked."""
        cl = LocalCluster(2, attrs={"chaos_drop": 1.0, "chaos_seed": 3,
                                    "eager_max_bytes": 0,
                                    "retry_limit": 1_000_000})
        try:
            scq = cl[0].alloc_cq()
            cq = cl[1].alloc_cq()
            rc = cl[1].register_rcomp(cq)
            for i in range(5):
                post_am(cl[0], 1, np.zeros(32, np.uint8),
                        local_comp=scq, remote_comp=rc, tag=i)
            assert cl[0].pending_ops
            cl[0].mark_peer_dead(1)
            deadline = time.monotonic() + 10.0
            codes = []
            while len(codes) < 5 and time.monotonic() < deadline:
                cl[0].progress()
                s = scq.pop()
                if not s.is_retry():
                    codes.append(s.code)
            assert codes == [ErrorCode.ERR_PEER_DEAD] * 5
            assert not cl[0].pending_ops
        finally:
            cl.close()


# ---------------------------------------------------------------------------
# codec hardening: corrupted bytes raise CodecError, never leak
# ---------------------------------------------------------------------------

def _sample_msg():
    return WireMsg(WireKind.EAGER_AM, 0, 1, tag=42,
                   payload=np.arange(24, dtype=np.uint8), size=24,
                   rcomp=3, device_index=1, seq=7, epoch=1)


class TestCodecFuzz:
    def test_roundtrip(self):
        frame = encode_msg(_sample_msg())
        msg, off = decode_msg(frame)
        assert off == len(frame)
        assert msg.tag == 42 and msg.seq == 7 and msg.epoch == 1
        np.testing.assert_array_equal(msg.payload,
                                      np.arange(24, dtype=np.uint8))

    def test_truncation_every_length(self):
        frame = encode_msg(_sample_msg())
        for n in range(len(frame)):
            with pytest.raises(CodecError):
                decode_msg(frame[:n])

    def test_bad_magic_and_version(self):
        frame = bytearray(encode_msg(_sample_msg()))
        bad = bytes([frame[0] ^ 0xFF]) + bytes(frame[1:])
        with pytest.raises(CodecError, match="magic"):
            decode_msg(bad)
        frame[2] ^= 0x55                          # version byte
        with pytest.raises(CodecError, match="version|magic"):
            decode_msg(bytes(frame))

    @settings(max_examples=60, deadline=None)
    @given(st.integers(0, 2 ** 31 - 1))
    def test_single_bit_flips_never_leak(self, seed):
        """Any one-bit corruption either still parses to a message or
        raises CodecError — never struct.error / IndexError / ValueError.
        Payload-body flips are always *caught* (the crc32)."""
        import random
        rng = random.Random(seed)
        frame = bytearray(encode_msg(_sample_msg()))
        pos = rng.randrange(len(frame))
        frame[pos] ^= 1 << rng.randrange(8)
        body_start = len(frame) - 24              # _P_BYTES raw payload
        try:
            decode_msg(bytes(frame))
        except CodecError:
            return                                # typed failure: fine
        # parsed: the flip must have hit a header field the crc does not
        # cover — payload corruption can never slip through
        assert pos < body_start

    def test_torn_concatenation(self):
        """Frames back to back parse cleanly; a torn second frame fails
        typed, leaving the first intact."""
        a, b = encode_msg(_sample_msg()), encode_msg(_sample_msg())
        both = a + b[: len(b) // 2]
        msg, off = decode_msg(both)
        assert msg.tag == 42 and off == len(a)
        with pytest.raises(CodecError):
            decode_msg(both, off)


# ---------------------------------------------------------------------------
# recovery machinery: straggler window, cfg-aware shrink
# ---------------------------------------------------------------------------

class TestStragglerWindow:
    def test_consecutive_stragglers_both_flagged(self):
        """Regression: flagged samples stay out of the window, so two
        slow steps in a row cannot normalize each other."""
        from repro.distributed.straggler import StepTimeMonitor
        mon = StepTimeMonitor(window=20, z_threshold=3.0, warmup=5)
        for i in range(10):
            mon.record(i, 1.0 + 0.001 * (i % 3))
        assert mon.record(10, 5.0) is not None
        assert mon.record(11, 5.0) is not None    # second one still seen
        assert len(mon.flagged) == 2
        # the baseline is uncontaminated: a normal step is not flagged
        assert mon.record(12, 1.001) is None


class TestShrinkMeshCfg:
    def test_cfg_snaps_to_compatible(self):
        from repro.configs.gemma3_1b import SMOKE
        from repro.distributed.elastic import (compatible_meshes,
                                               shrink_mesh)
        shape = shrink_mesh((4, 2), 0.25, SMOKE)   # 8 -> target 6
        n = shape[0] * shape[1]
        assert n <= 6
        assert tuple(shape) in {(d, m) for d, m in
                                compatible_meshes(SMOKE, n)}

    def test_cfg_none_keeps_model_axis(self):
        from repro.distributed.elastic import shrink_mesh
        assert shrink_mesh((4, 2), 0.5) == (2, 2)

    def test_prefers_old_model_width(self):
        """Among equal device counts the old model width wins — the
        cheapest re-shard keeps the TP axis in place."""
        from repro.configs.gemma3_1b import SMOKE
        from repro.distributed.elastic import compatible_meshes, shrink_mesh
        shape = shrink_mesh((2, 2), 0.0, SMOKE)    # nothing died
        assert shape[0] * shape[1] == 4
        if (2, 2) in compatible_meshes(SMOKE, 4):
            assert shape == (2, 2)

    def test_incompatible_raises(self, monkeypatch):
        """Survivors that cannot host the model at any width get a typed
        error, not a silent bad mesh."""
        from repro.configs.gemma3_1b import SMOKE
        from repro.distributed import elastic
        monkeypatch.setattr(elastic, "compatible_meshes",
                            lambda cfg, n: [])
        with pytest.raises(ValueError, match="no mesh"):
            elastic.shrink_mesh((4, 2), 0.5, SMOKE)


# ---------------------------------------------------------------------------
# crash safety: mid-commit kill, spmd rank death
# ---------------------------------------------------------------------------

class TestMidCommitKill:
    def test_kill_during_commit_keeps_prior_checkpoint(self, tmp_path):
        from repro.checkpoint import latest_step, restore
        ckpt = str(tmp_path / "ckpt")
        proc = subprocess.Popen(
            [sys.executable, os.path.join(HELPERS, "ckpt_kill.py"), ckpt],
            stdout=subprocess.PIPE, text=True, env=_child_env())
        try:
            marker = proc.stdout.readline()
            assert "COMMITTING" in marker, marker
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()
        assert proc.returncode == -signal.SIGKILL
        # the torn step-1 commit is invisible: LATEST still says 0 and
        # the restore verifies hashes cleanly
        assert latest_step(ckpt) == 0
        like = {"w": np.zeros(64, np.float64), "step": np.zeros((),
                                                               np.int64)}
        got, manifest = restore(ckpt, like)
        assert manifest["step"] == 0
        np.testing.assert_array_equal(got["w"],
                                      np.arange(64, dtype=np.float64))
        assert not os.path.exists(os.path.join(ckpt, "step_00000001"))


@pytest.mark.slow
class TestSpmdChaosKill:
    def test_rank_kill_recovers(self, tmp_path):
        """2-rank spmd job, launcher SIGKILLs rank 1 mid-stream: the
        survivor detects via heartbeat, completes outstanding posts as
        ERR_PEER_DEAD, shrinks the mesh, restores resharded — exit 0."""
        env = _child_env()
        env.setdefault("REPRO_ATTR_FABRIC_BACKEND", "shm")
        r = subprocess.run(
            [sys.executable, "-m", "repro.launch.spmd", "--ranks", "2",
             "--chaos-kill", "1", "--kill-after", "0.5",
             "--hb-timeout", "1.0", "--timeout", "120"],
            capture_output=True, text=True, timeout=180, env=env)
        out = r.stdout + r.stderr
        assert r.returncode == 0, out
        assert "peer_dead" in out and "recovered" in out, out
