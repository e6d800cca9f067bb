"""SPMD launcher (launch/spmd.py): bootstrap env exchange, the mmap
generation-counter barrier, the happy-path 2-process window demo, and —
the teardown satellite — rank death mid-window: the launcher must reap
the process group, surface a nonzero exit, and never hang (every join
here is timeout-bounded, matching the tests/test_concurrency.py
discipline)."""
import os
import subprocess
import sys
import threading
import time

import pytest

from repro.launch import spmd

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _env_without_spmd():
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("REPRO_SPMD_")}
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    return env


class TestBootstrap:
    def test_requires_launcher_env(self, monkeypatch):
        monkeypatch.delenv(spmd.RANK_ENV, raising=False)
        with pytest.raises(RuntimeError, match="REPRO_SPMD_RANK"):
            spmd.bootstrap()

    def test_reads_launcher_env(self, monkeypatch, tmp_path):
        monkeypatch.setenv(spmd.RANK_ENV, "1")
        monkeypatch.setenv(spmd.NRANKS_ENV, "4")
        monkeypatch.setenv(spmd.SESSION_ENV, str(tmp_path))
        ctx = spmd.bootstrap()
        assert (ctx.rank, ctx.n_ranks) == (1, 4)
        assert ctx.session == str(tmp_path)


class TestBarrier:
    def test_two_ranks_meet(self, tmp_path):
        ctxs = [spmd.SpmdContext(r, 2, str(tmp_path)) for r in range(2)]
        errs = []

        def arrive(ctx):
            try:
                for _ in range(5):       # generations advance in lockstep
                    ctx.barrier(timeout=20.0)
            except Exception as e:       # pragma: no cover - failure path
                errs.append(e)

        threads = [threading.Thread(target=arrive, args=(c,))
                   for c in ctxs]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive(), "barrier thread wedged"
        assert not errs
        for c in ctxs:
            c.close()

    def test_lone_rank_times_out(self, tmp_path):
        ctx = spmd.SpmdContext(0, 2, str(tmp_path))
        with pytest.raises(TimeoutError, match="barrier"):
            ctx.barrier(timeout=0.2)
        ctx.close()


class TestLauncher:
    @pytest.mark.parametrize("backend", ["shm", "socket"])
    def test_two_process_window_demo(self, backend):
        """The acceptance smoke: 2 OS-process ranks run the message
        window cross-process with lost=0 / leaked=0."""
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.spmd", "--ranks", "2",
             "--backend", backend, "--iters", "5", "--window", "16",
             "--timeout", "90"],
            env=_env_without_spmd(), capture_output=True, text=True,
            timeout=120)
        assert out.returncode == 0, out.stderr + out.stdout
        # ranks share stdout, so lines may interleave — count substrings
        assert out.stdout.count("spmd-demo rank") == 2
        assert out.stdout.count("lost=0 leaked=0") == 2

    def test_attr_overrides_reach_children(self, tmp_path):
        probe = ("import os, sys; sys.path.insert(0, os.environ['SRC']); "
                 "from repro.core import LocalCluster; "
                 "cl = LocalCluster(2); "
                 "assert cl.fabric.depth == 123, cl.fabric.depth; "
                 "assert cl.fabric.attr_source('fabric_depth') == 'env'")
        env = _env_without_spmd()
        env["SRC"] = SRC
        old = dict(os.environ)
        os.environ.update(env)
        try:
            code = spmd.launch([sys.executable, "-c", probe], 2,
                               backend="shm",
                               attr_overrides={"fabric_depth": "123"},
                               timeout=60)
        finally:
            os.environ.clear()
            os.environ.update(old)
        assert code == 0

    def test_ranks_never_open_the_accelerator(self, monkeypatch):
        """Ranks are host-only: whatever platform the parent asks for,
        every child runs with JAX_PLATFORMS=cpu, so no rank can take a
        chip its parent (or a server beside it) holds."""
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        probe = ("import os, sys; "
                 "sys.exit(os.environ.get('JAX_PLATFORMS') != 'cpu')")
        assert spmd.launch([sys.executable, "-c", probe], 2,
                           backend="shm", timeout=60) == 0
        env = spmd._child_env(0, 2, "/nonexistent", "shm", {})
        assert env["JAX_PLATFORMS"] == "cpu"

    def test_rank_death_reaps_group_nonzero_exit(self):
        """Satellite: one rank dies mid-window (exit 3) while its peer
        would happily spin forever; the launcher must kill the survivor's
        whole process group, return nonzero, and come back well under the
        join bound."""
        victim = (
            "import os, sys, time\n"
            "sys.path.insert(0, os.environ['SRC'])\n"
            "from repro.launch.spmd import bootstrap\n"
            "ctx = bootstrap()\n"
            "ctx.barrier(timeout=30)\n"
            "if ctx.rank == 1:\n"
            "    os._exit(3)\n"          # death mid-window
            "# rank 0: a grandchild too — group kill must reap it\n"
            "import subprocess\n"
            "child = subprocess.Popen([sys.executable, '-c',\n"
            "                          'import time; time.sleep(600)'])\n"
            "open(os.path.join(ctx.session_keep, 'grandchild'),\n"
            "     'w').write(str(child.pid))\n"
            "while True:\n"
            "    time.sleep(0.1)\n"      # spins until the launcher kills us
        )
        # stash the grandchild pid OUTSIDE the session dir (the launcher
        # removes the session on teardown)
        victim = victim.replace("ctx.session_keep",
                                "os.environ['PIDDIR']")
        env = _env_without_spmd()
        env["SRC"] = SRC
        import tempfile
        piddir = tempfile.mkdtemp(prefix="spmd-test-")
        env["PIDDIR"] = piddir
        old = dict(os.environ)
        os.environ.update(env)
        t0 = time.monotonic()
        try:
            code = spmd.launch([sys.executable, "-c", victim], 2,
                               backend="shm", timeout=60)
        finally:
            os.environ.clear()
            os.environ.update(old)
        elapsed = time.monotonic() - t0
        assert code == 3                  # the dead rank's exit surfaced
        assert elapsed < 45, f"teardown took {elapsed:.1f}s"
        # the survivor's grandchild must be gone too (process-group kill)
        pid_file = os.path.join(piddir, "grandchild")
        deadline = time.monotonic() + 10
        reaped = False
        while time.monotonic() < deadline:
            if not os.path.exists(pid_file):
                reaped = True             # rank 0 died before spawning it
                break
            pid = int(open(pid_file).read())
            try:
                os.kill(pid, 0)
            except ProcessLookupError:
                reaped = True
                break
            time.sleep(0.1)
        import shutil
        shutil.rmtree(piddir, ignore_errors=True)
        assert reaped, "grandchild survived the process-group teardown"

    def test_timeout_kills_everything(self):
        hang = ("import os, sys, time\n"
                "time.sleep(600)\n")
        env = _env_without_spmd()
        old = dict(os.environ)
        os.environ.update(env)
        t0 = time.monotonic()
        try:
            code = spmd.launch([sys.executable, "-c", hang], 2,
                               backend="shm", timeout=2.0)
        finally:
            os.environ.clear()
            os.environ.update(old)
        assert code == 124
        assert time.monotonic() - t0 < 30
