"""Concurrency subsystem (paper §4.1/§4.2) — invariants under real threads.

Every stress test here is timeout-bounded (threads are joined with a
deadline and the test fails loudly if one is stuck) so a deadlock in the
lock discipline fails fast instead of hanging CI.

Covered invariants:
* TryLock — mutual exclusion, contention counting, spin-backoff fallback.
* Atomics — exact counts under N incrementing threads, CAS semantics,
  bounded credits never oversubscribe.
* LCQ — no lost or duplicated items through N producers / M consumers.
* HostPacketPool — no double-allocated packet ids under concurrent
  get/put/steal; conservation of packets.
* HostMatchingEngine — per-bucket insert linearizability (every match
  pairs exactly one send with one recv; nothing matched twice).
* BacklogQueue — thread-safe, and ``push_front`` redelivery can never
  fail at capacity (regression: a full backlog must still redeliver in
  FIFO order).
* ProgressWorkerPool / EndpointSpec(progress="workers") — worker threads
  drive real traffic to completion with zero losses.
* ServeScheduler.start_result_drain — results drained from worker
  threads arrive exactly once.
"""
from __future__ import annotations

import dataclasses
import random
import threading
import time

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (LCQ, AtomicCounter, AtomicCredit, AtomicFlag,
                        BacklogQueue, CommConfig, EndpointSpec, FatalError,
                        HostMatchingEngine, HostPacketPool, LocalCluster,
                        MatchKind, ProgressWorkerPool,
                        ThreadSafeCompletionQueue, TryLock, done, post_am_x)
from repro.core.concurrency.lcq import drain as lcq_drain
from repro.core.packet_pool import init_pool, pool_get
from repro.core.status import ErrorCode

JOIN_TIMEOUT = 30.0          # any thread alive after this = deadlock = fail


def run_threads(fns, timeout=JOIN_TIMEOUT):
    """Start one thread per fn, join with a deadline, surface errors."""
    errors = []

    def wrap(fn):
        def inner():
            try:
                fn()
            except BaseException as e:                   # re-raised below
                errors.append(e)
        return inner

    threads = [threading.Thread(target=wrap(fn), daemon=True)
               for fn in fns]
    for t in threads:
        t.start()
    deadline = time.monotonic() + timeout
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    stuck = [t for t in threads if t.is_alive()]
    assert not stuck, f"threads wedged (deadlock?): {stuck}"
    if errors:
        raise errors[0]


@pytest.fixture(autouse=True)
def fast_gil_switching():
    """Preempt every 50us so threads really interleave inside critical
    sections — otherwise CPython's 5ms default hides most races."""
    import sys
    old = sys.getswitchinterval()
    sys.setswitchinterval(5e-5)
    yield
    sys.setswitchinterval(old)


# ---------------------------------------------------------------------------
# locks
# ---------------------------------------------------------------------------

class TestTryLock:
    def test_try_acquire_counts_contention(self):
        lk = TryLock(name="t")
        assert lk.try_acquire()
        assert not lk.try_acquire()      # non-reentrant: second try fails
        assert lk.contentions == 1
        lk.release()
        assert lk.try_acquire()
        lk.release()
        assert lk.acquisitions == 2

    def test_reentrant_variant(self):
        lk = TryLock(name="r", reentrant=True)
        with lk:
            with lk:                      # same thread: legal
                pass
        # another thread cannot take it while held
        lk.acquire()
        saw = []
        run_threads([lambda: saw.append(lk.try_acquire())])
        lk.release()
        assert saw == [False]

    def test_mutual_exclusion_under_stress(self):
        lk = TryLock(name="mx")
        counter = {"v": 0}               # plain int: the lock protects it
        N, T = 2000, 4

        def worker():
            for _ in range(N):
                lk.acquire()             # spin-backoff blocking path
                counter["v"] += 1
                lk.release()

        run_threads([worker] * T)
        assert counter["v"] == N * T
        assert lk.acquisitions == N * T

    def test_stats_shape(self):
        lk = TryLock(name="s")
        row = lk.stats()
        assert set(row) == {"name", "acquisitions", "contentions", "spins"}


# ---------------------------------------------------------------------------
# atomics
# ---------------------------------------------------------------------------

class TestAtomics:
    def test_counter_exact_under_threads(self):
        c = AtomicCounter()
        N, T = 5000, 4
        run_threads([lambda: [c.fetch_add(1) for _ in range(N)]] * T)
        assert c.load() == N * T

    def test_fetch_add_tickets_unique(self):
        c = AtomicCounter()
        tickets = [[] for _ in range(4)]

        def taker(out):
            for _ in range(1000):
                out.append(c.fetch_add(1))

        run_threads([lambda o=o: taker(o) for o in tickets])
        flat = [t for chunk in tickets for t in chunk]
        assert sorted(flat) == list(range(4000))     # no dup, no gap

    def test_compare_exchange(self):
        c = AtomicCounter(5)
        assert not c.compare_exchange(4, 9)
        assert c.compare_exchange(5, 9)
        assert c.load() == 9

    def test_flag(self):
        f = AtomicFlag()
        assert not f.test_and_set()
        assert f.test_and_set()
        f.clear()
        assert not f.is_set()

    def test_credit_never_oversubscribes(self):
        cr = AtomicCredit(10)
        holders = AtomicCounter()
        peak = AtomicCounter()

        def worker():
            for _ in range(500):
                if cr.try_acquire():
                    n = holders.add(1)
                    # racy max is fine: only used as a lower bound probe
                    if n > peak.load():
                        peak.store(n)
                    assert n <= 10, "credit oversubscribed"
                    holders.add(-1)
                    cr.release()

        run_threads([worker] * 4)
        assert cr.used == 0
        assert peak.load() <= 10


# ---------------------------------------------------------------------------
# LCQ: the FAA fixed-size MPMC queue
# ---------------------------------------------------------------------------

class TestLCQ:
    def test_fifo_single_thread(self):
        q = LCQ(4)
        for i in range(4):
            assert q.push(i)
        assert not q.push(99)            # full -> non-blocking False
        assert [q.pop()[0] for _ in range(4)] == [0, 1, 2, 3]
        assert q.pop() == (None, False)  # empty
        # wrap-around lap
        assert q.push(7) and q.pop() == (7, True)

    def test_no_lost_no_dup_mpmc(self):
        """N producers, M consumers: every pushed item popped exactly once."""
        q = LCQ(64)                      # small: forces full/empty races
        NP, NC, PER = 4, 4, 3000
        popped = [[] for _ in range(NC)]
        produced = AtomicCounter()
        done_flag = AtomicFlag()

        def producer(base):
            for i in range(PER):
                item = base * PER + i
                while not q.push(item):
                    time.sleep(1e-6)     # full: back off, never drop
                produced.fetch_add(1)

        def consumer(out):
            while True:
                item, ok = q.pop()
                if ok:
                    out.append(item)
                elif done_flag.is_set() and not len(q):
                    item, ok = q.pop()   # final race-free sweep
                    if ok:
                        out.append(item)
                    else:
                        return
                else:
                    time.sleep(1e-6)

        producers = [lambda b=b: producer(b) for b in range(NP)]

        def run_all():
            errors = []
            cthreads = [threading.Thread(target=lambda o=o: consumer(o),
                                         daemon=True) for o in popped]
            for t in cthreads:
                t.start()
            run_threads(producers)
            done_flag.test_and_set()
            deadline = time.monotonic() + JOIN_TIMEOUT
            for t in cthreads:
                t.join(max(0.0, deadline - time.monotonic()))
            assert not any(t.is_alive() for t in cthreads), "consumer stuck"

        run_all()
        flat = sorted(x for chunk in popped for x in chunk)
        assert flat == list(range(NP * PER)), (
            f"lost={NP * PER - len(flat)} or duplicated")

    def test_push_many_pop_many_single_thread(self):
        q = LCQ(8)
        assert q.push_many(list(range(5))) == 5
        assert q.pop_many(3) == [0, 1, 2]
        assert q.push_many(list(range(5, 12))) == 6   # only 6 slots free
        assert q.pop_many() == [3, 4, 5, 6, 7, 8, 9, 10]
        assert q.pop_many() == []                     # empty
        # scalar/batch interleave across wrap-around laps
        for _ in range(5):
            assert q.push(99)
            assert q.push_many([1, 2]) == 2
            assert q.pop() == (99, True)
            assert q.pop_many() == [1, 2]

    def test_push_many_full_accepts_zero(self):
        q = LCQ(4)
        assert q.push_many([0, 1, 2, 3]) == 4
        assert q.push_many([9, 9]) == 0               # full, nothing lost
        assert q.pop_many() == [0, 1, 2, 3]

    def test_batch_mpmc_no_lost_no_dup(self):
        """Mixed scalar/batch producers and consumers: every item popped
        exactly once (the single-CAS bulk ticket claims must not double-
        grant or skip slots under contention)."""
        q = LCQ(64)
        NP, NC, PER = 4, 4, 3000
        popped = [[] for _ in range(NC)]
        done_flag = AtomicFlag()

        def producer(base):
            rng = random.Random(base)
            i = 0
            while i < PER:
                hi = min(i + rng.randint(1, 7), PER)
                if rng.random() < 0.3:
                    if q.push(base * PER + i):
                        i += 1
                else:
                    i += q.push_many([base * PER + j
                                      for j in range(i, hi)])
                if i < PER:
                    time.sleep(0)

        def consumer(out):
            rng = random.Random(id(out))
            while True:
                got = q.pop_many(rng.randint(1, 9))
                if got:
                    out.extend(got)
                elif done_flag.is_set() and not len(q):
                    out.extend(q.pop_many())          # final sweep
                    if not len(q):
                        return
                else:
                    time.sleep(1e-6)

        cthreads = [threading.Thread(target=lambda o=o: consumer(o),
                                     daemon=True) for o in popped]
        for t in cthreads:
            t.start()
        run_threads([lambda b=b: producer(b) for b in range(NP)])
        done_flag.test_and_set()
        deadline = time.monotonic() + JOIN_TIMEOUT
        for t in cthreads:
            t.join(max(0.0, deadline - time.monotonic()))
        assert not any(t.is_alive() for t in cthreads), "consumer stuck"
        flat = sorted(x for chunk in popped for x in chunk)
        assert flat == list(range(NP * PER)), (
            f"lost={NP * PER - len(flat)} or duplicated")

    def test_threadsafe_cq_signal_many_prefix(self):
        cq = ThreadSafeCompletionQueue(capacity=16)
        res = cq.signal_many([done(tag=i) for i in range(20)])
        assert [r.is_retry() for r in res] == [False] * 16 + [True] * 4
        assert all(r.code == ErrorCode.RETRY_QUEUE_FULL for r in res[16:])
        assert [s.tag for s in cq.pop_many()] == list(range(16))
        assert cq.pop_many() == []
        assert lcq_drain(cq) == []                    # bulk drain path

    def test_threadsafe_cq_protocol(self):
        cq = ThreadSafeCompletionQueue(capacity=2)
        assert cq.signal(done(1)).is_done()
        assert cq.signal(done(2)).is_done()
        st = cq.signal(done(3))
        assert st.is_retry() and st.code == ErrorCode.RETRY_QUEUE_FULL
        ready, _ = cq.test()
        assert ready
        assert cq.pop().get_buffer() == 1        # FIFO
        assert cq.signal(done(3)).is_done()      # slot freed


# ---------------------------------------------------------------------------
# packet pool under concurrent get/put/steal
# ---------------------------------------------------------------------------

class TestPacketPoolThreaded:
    def test_no_double_allocation(self):
        """Under concurrent get/put/steal no packet id is ever held by two
        lanes at once, and every packet survives the churn."""
        pool = HostPacketPool(n_lanes=4, packets_per_lane=8)
        in_use = [AtomicFlag() for _ in range(pool.n_packets)]
        T, N = 4, 4000

        def worker(lane):
            held = []
            for i in range(N):
                pkt, st = pool.get(lane)
                if st.is_done():
                    assert not in_use[pkt].test_and_set(), (
                        f"packet {pkt} double-allocated")
                    held.append(pkt)
                if held and (i % 3 == 0 or len(held) > 4):
                    p = held.pop()
                    in_use[p].clear()
                    pool.put(lane, p)
            for p in held:
                in_use[p].clear()
                pool.put(lane, p)

        run_threads([lambda l=l: worker(l) for l in range(T)])
        assert pool.free_packets() == pool.n_packets, "packets leaked"
        assert pool.gets == T * N

    def test_steal_failure_is_retry_not_block(self):
        pool = HostPacketPool(n_lanes=2, packets_per_lane=4)
        # empty lane 0 so a get must steal from lane 1
        for _ in range(4):
            pool.get(0)
        # hold lane 1's lock from "another thread"
        acquired = []
        release = threading.Event()

        def holder():
            pool.locks[1].acquire()
            acquired.append(True)
            release.wait(JOIN_TIMEOUT)
            pool.locks[1].release()

        t = threading.Thread(target=holder, daemon=True)
        t.start()
        while not acquired:
            time.sleep(1e-4)
        pkt, st = pool.get(0)            # must not block on the victim
        release.set()
        t.join(JOIN_TIMEOUT)
        assert pkt == -1 and st.is_retry()
        assert st.code == ErrorCode.RETRY_NOPACKET
        assert pool.steal_lock_failures == 1


# ---------------------------------------------------------------------------
# functional pool: victim selection property (satellite fix)
# ---------------------------------------------------------------------------

class TestPoolGetVictim:
    @settings(max_examples=60, deadline=None)
    @given(st.integers(min_value=1, max_value=8),
           st.integers(min_value=0, max_value=7),
           st.integers(min_value=-(2 ** 31), max_value=2 ** 31 - 1))
    def test_victim_never_self(self, n_lanes, lane, seed):
        """For every (lanes, lane, seed) — including negative seeds — the
        steal path either succeeds from a *different* lane or retries;
        the chosen victim never aliases the caller's own lane."""
        lane = lane % n_lanes
        pool = init_pool(n_lanes, packets_per_lane=2)
        # empty the caller's lane so get() takes the steal path
        pool, a, _ = pool_get(pool, lane, 0)
        pool, b, _ = pool_get(pool, lane, 0)
        pool, pid, status = pool_get(pool, lane, seed)
        if n_lanes == 1:
            assert int(status) == 1      # only retry is possible
            return
        # mirror of the host formula, with the explicit non-negative mod
        offset = seed % max(n_lanes - 1, 1)
        victim = (lane + 1 + offset) % n_lanes
        assert victim != lane
        if int(status) == 0:
            assert int(pid) >= 0
            # the packet really came from the victim's seeded range
            assert int(pid) // 2 != lane or int(pid) in (int(a), int(b))


# ---------------------------------------------------------------------------
# matching engine linearizability
# ---------------------------------------------------------------------------

class TestMatchingThreaded:
    def test_insert_linearizable_per_bucket(self):
        """T threads concurrently insert sends+recvs on shared keys; every
        match must pair exactly one send with one recv — no value matched
        twice, none invented, and counts must reconcile."""
        me = HostMatchingEngine(n_buckets=16)
        T, PER_KEY = 4, 500
        keys = [("k", i) for i in range(8)]
        matched = [[] for _ in range(2 * T)]

        def inserter(kind, out, base):
            for i in range(PER_KEY):
                key = keys[i % len(keys)]
                got = me.insert(key, kind, (kind.name, base, i))
                if got is not None:
                    out.append(got)

        fns = []
        for t in range(T):
            fns.append(lambda o=matched[2 * t], b=t:
                       inserter(MatchKind.SEND, o, b))
            fns.append(lambda o=matched[2 * t + 1], b=t:
                       inserter(MatchKind.RECV, o, b))
        run_threads(fns)

        flat = [v for chunk in matched for v in chunk]
        assert len(set(flat)) == len(flat), "a value was matched twice"
        # a SEND insert returns a RECV value and vice versa
        assert me.matches == len(flat)
        assert me.inserts == 2 * T * PER_KEY
        assert me.pending() == me.inserts - 2 * me.matches


# ---------------------------------------------------------------------------
# backlog queue (incl. the push_front capacity-bypass regression)
# ---------------------------------------------------------------------------

class TestBacklogThreaded:
    def test_push_front_bypasses_capacity(self):
        """Regression: a full backlog must still accept a redelivery —
        push_front is a requeue of an already-admitted item and can never
        fail — and FIFO order must survive."""
        bq = BacklogQueue(capacity=2)
        assert bq.push("a").is_done()
        assert bq.push("b").is_done()
        assert bq.push("c").is_retry()           # tail respects capacity
        item, st = bq.pop()
        assert item == "a" and st.is_done()
        assert bq.push("x").is_done()            # full again: a,b -> b,x
        assert bq.push_front("a").is_done()      # redelivery MUST succeed
        assert len(bq) == 3                      # transiently over capacity
        order = []
        while True:
            item, st = bq.pop()
            if st.is_retry():
                break
            order.append(item)
        assert order == ["a", "b", "x"], "redelivery broke FIFO"

    def test_thread_safe_push_pop(self):
        bq = BacklogQueue()
        T, N = 4, 2000
        popped = [[] for _ in range(T)]
        stop = AtomicFlag()

        def producer(base):
            for i in range(N):
                assert bq.push((base, i)).is_done()

        def consumer(out):
            while True:
                item, st = bq.pop()
                if st.is_done():
                    out.append(item)
                elif stop.is_set() and bq.empty_flag:
                    return
                else:
                    time.sleep(1e-6)

        cthreads = [threading.Thread(target=lambda o=o: consumer(o),
                                     daemon=True) for o in popped]
        for t in cthreads:
            t.start()
        run_threads([lambda b=b: producer(b) for b in range(T)])
        stop.test_and_set()
        deadline = time.monotonic() + JOIN_TIMEOUT
        for t in cthreads:
            t.join(max(0.0, deadline - time.monotonic()))
        assert not any(t.is_alive() for t in cthreads)
        flat = [x for chunk in popped for x in chunk]
        assert sorted(flat) == sorted((b, i) for b in range(T)
                                      for i in range(N))


# ---------------------------------------------------------------------------
# progress workers end-to-end
# ---------------------------------------------------------------------------

def _post_all(r0, rc, n, dev=None, payload=None):
    payload = payload if payload is not None else np.zeros(8, np.uint8)
    sent = 0
    while sent < n:
        x = post_am_x(r0, 1, payload, None, None, rc)
        if dev is not None:
            x = x.device(dev)
        if not x().is_retry():
            sent += 1
        else:
            time.sleep(1e-5)
    return sent


class TestProgressWorkers:
    def test_worker_pool_delivers_everything(self):
        """Main thread posts; the worker pool alone drives all progress."""
        cfg = CommConfig(inject_max_bytes=1, packets_per_lane=64)
        cl = LocalCluster(2, cfg, fabric_depth=1 << 14)
        r0, r1 = cl[0], cl[1]
        cq = r1.alloc_cq(threadsafe=True)
        rc = r1.register_rcomp(cq)
        N = 500
        with cl.alloc_workers(n_workers=3):
            _post_all(r0, rc, N)
            deadline = time.monotonic() + JOIN_TIMEOUT
            while cq.pushes < N:
                assert time.monotonic() < deadline, (
                    f"workers stalled: {cq.pushes}/{N}")
                time.sleep(1e-4)
        assert cq.pushes == N, "lost completions"
        cl.quiesce()
        assert r0.packet_pool.free_packets() == r0.packet_pool.n_packets

    def test_try_progress_skips_held_device(self):
        cl = LocalCluster(2)
        r0, r1 = cl[0], cl[1]
        dev = r0.default_device
        # deliverable work on the device's incoming stream: an idle
        # device short-circuits to False before consulting the lock,
        # and this test is about the try-lock discipline
        cq = r0.alloc_cq(threadsafe=True)
        rc = r0.register_rcomp(cq)
        while post_am_x(r1, 0, np.zeros(8, np.uint8), None, None,
                        rc)().is_retry():
            time.sleep(1e-5)
        dev.progress_lock.acquire()
        held = []
        run_threads([lambda: held.append(r0.engine.try_progress(dev))])
        dev.progress_lock.release()
        assert held == [None]            # moved on, did not block
        assert r0.engine.try_progress(dev) is not None

    def test_try_progress_idle_fast_path(self):
        """An idle device reports False without taking the progress
        lock — even when another thread holds it."""
        cl = LocalCluster(2)
        r0 = cl[0]
        dev = r0.default_device
        dev.progress_lock.acquire()
        try:
            acqs = dev.progress_lock.stats()["acquisitions"]
            assert r0.engine.try_progress(dev) is False
            assert dev.progress_lock.stats()["acquisitions"] == acqs
        finally:
            dev.progress_lock.release()

    def test_endpoint_workers_spec(self):
        cfg = CommConfig(inject_max_bytes=1, packets_per_lane=64,
                         n_channels=2)
        cl = LocalCluster(2, cfg, fabric_depth=1 << 14)
        r0, r1 = cl[0], cl[1]
        spec = EndpointSpec(name="w", n_devices=2, progress="workers",
                            n_workers=2)
        ep0 = r0.alloc_endpoint(spec=spec)
        ep1 = r1.alloc_endpoint(spec=dataclasses.replace(spec, name="w1"))
        cq = r1.alloc_cq(threadsafe=True)
        rc = r1.register_rcomp(cq)
        N = 300
        with ep0, ep1:
            sent = 0
            while sent < N:
                if not ep0.post_am(1, np.zeros(8, np.uint8),
                                   remote_comp=rc).is_retry():
                    sent += 1
                else:
                    time.sleep(1e-5)
            deadline = time.monotonic() + JOIN_TIMEOUT
            while cq.pushes < N:
                assert time.monotonic() < deadline, "endpoint workers stalled"
                time.sleep(1e-4)
        assert cq.pushes == N
        counters = ep0.counters()
        assert counters["workers"]["n_workers"] == 2
        assert not ep0.workers.running   # context manager stopped them

    def test_workers_spec_validation(self):
        with pytest.raises(FatalError):
            EndpointSpec(progress="shared", n_workers=2)
        with pytest.raises(FatalError):
            EndpointSpec(progress="workers", n_workers=-1)
        cl = LocalCluster(1)
        ep = cl[0].alloc_endpoint(progress="shared")
        with pytest.raises(FatalError):
            ep.start_workers()

    def test_free_endpoint_stops_workers(self):
        cl = LocalCluster(1)
        ep = cl[0].alloc_endpoint(progress="workers", n_devices=1)
        ep.start_workers()
        assert ep.workers.running
        cl[0].free_endpoint(ep)
        assert not ep.workers.running


# ---------------------------------------------------------------------------
# scheduler result drain from worker threads
# ---------------------------------------------------------------------------

class TestSchedulerDrain:
    def _sched(self, max_batch=8):
        from repro.serving import PagedKVAllocator, ServeScheduler

        def decode_fn(tokens, positions):
            return np.asarray(tokens) + 1

        return ServeScheduler(decode_fn, max_batch=max_batch,
                              allocator=PagedKVAllocator(n_pages=64,
                                                         page_size=16))

    def test_results_drained_exactly_once(self):
        sched = self._sched()
        cq = sched.alloc_cq(threadsafe=True)
        N = 24
        for _ in range(N):
            sched.submit(np.array([1, 2, 3]), max_new=4, comp=cq,
                         allow_retry=False)
        drain = sched.start_result_drain(cq, n_workers=3)
        deadline = time.monotonic() + JOIN_TIMEOUT
        while sched.completed < N:
            assert time.monotonic() < deadline, "scheduler stalled"
            sched.step()
        results = drain.stop()
        assert len(results) == N, "a result was lost or duplicated"
        rids = [st.tag for st in results]
        assert len(set(rids)) == N

    def test_drain_requires_threadsafe_cq(self):
        sched = self._sched()
        with pytest.raises(FatalError):
            sched.start_result_drain(sched.alloc_cq(), n_workers=2)


# ---------------------------------------------------------------------------
# engine-level: no lost completions through the full posting path
# ---------------------------------------------------------------------------

class TestEndToEndStress:
    def test_posters_and_workers_no_lost_completions(self):
        """T poster threads + a worker pool, bufcopy protocol, small pool:
        steals, retries, and backlog all exercised; exact delivery count
        and full packet-pool conservation at the end."""
        T, PER = 3, 400
        cfg = CommConfig(inject_max_bytes=1, packets_per_lane=16,
                         n_channels=T)
        cl = LocalCluster(2, cfg, fabric_depth=256)
        r0, r1 = cl[0], cl[1]
        devs = [r0.alloc_device() for _ in range(T)]
        [r1.alloc_device() for _ in range(T)]
        cq = r1.alloc_cq(threadsafe=True)
        rc = r1.register_rcomp(cq)

        with cl.alloc_workers(n_workers=2):
            run_threads([lambda d=d: _post_all(r0, rc, PER, dev=d)
                         for d in devs])
            deadline = time.monotonic() + JOIN_TIMEOUT
            while cq.pushes < T * PER:
                assert time.monotonic() < deadline, (
                    f"stalled at {cq.pushes}/{T * PER}")
                time.sleep(1e-4)
        assert cq.pushes == T * PER
        cl.quiesce()
        assert r0.packet_pool.free_packets() == r0.packet_pool.n_packets
