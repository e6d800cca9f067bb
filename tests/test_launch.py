"""The launchers' callable forms (what the CLIs and ``chip_smoke.py``
share), the compile-cache placement, and the dry run's peak table."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.launch import serve as serve_launch
from repro.launch import train as train_launch
from repro.launch.compile_cache import DEFAULT_DIR, ENV

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SRC = os.path.join(REPO, "src")


@pytest.fixture(scope="module")
def olmo_smoke():
    cfg, _, params = serve_launch.build("olmo-1b", smoke=True)
    return cfg, params


@pytest.mark.parametrize("transport,drain_workers",
                         [(True, 0), (False, 0), (False, 2)])
def test_serve_delivers_every_request_once(olmo_smoke, transport,
                                           drain_workers):
    cfg, params = olmo_smoke
    res = serve_launch.serve(cfg, params, requests=6, max_new=5,
                             max_batch=4, cache_len=32, transport=transport,
                             drain_workers=drain_workers)
    rids = [rid for rid, _ in res.received]
    assert sorted(rids) == sorted(res.submitted)
    assert len(set(rids)) == 6
    assert all(len(t) == 5 for _, t in res.received)
    assert res.n_tokens == 30
    assert all(0 <= int(x) < cfg.vocab for _, t in res.received for x in t)
    if transport:
        assert sum(res.prefill_posts) == 6
    else:
        assert res.prefill_posts is None


@pytest.mark.parametrize("kwargs,match", [
    ({"attrs": ["rdv_threshold=4096"]}, "needs --transport"),
    ({"transport": True, "drain_workers": 2}, "pick one"),
])
def test_serve_rejects_conflicting_options(olmo_smoke, kwargs, match):
    cfg, params = olmo_smoke
    with pytest.raises(ValueError, match=match):
        serve_launch.serve(cfg, params, **kwargs)


def test_serve_refuses_to_decode_past_the_cache(olmo_smoke):
    cfg, params = olmo_smoke
    with pytest.raises(RuntimeError, match="past cache_len"):
        serve_launch.serve(cfg, params, requests=2, max_new=8, max_batch=2,
                           cache_len=4)


def test_train_single_device_smoke():
    res = train_launch.train("olmo-1b", smoke=True, steps=3, seq=16,
                             batch=2)
    losses = [h["loss"] for h in res.history]
    assert len(losses) == 3 and np.isfinite(losses).all()
    assert len(res.state_bytes) == 1


def test_train_rejects_attrs_without_mesh():
    with pytest.raises(ValueError, match="needs --mesh"):
        train_launch.train("olmo-1b", smoke=True, steps=1,
                           attrs=["n_channels=2"])


def test_train_state_created_sharded_on_mesh(helper_runner):
    assert "HELPER-OK" in helper_runner("sharded_train", devices=4)


_CACHE_PROBE = (
    "import jax, jax.numpy as jnp\n"
    "from repro.launch.compile_cache import enable_compile_cache\n"
    "print(enable_compile_cache())\n"
    "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
    "jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()\n")


def _probe_cache(env_dir):
    env = {k: v for k, v in os.environ.items() if k != ENV}
    env["PYTHONPATH"] = SRC
    if env_dir is not None:
        env[ENV] = env_dir
    r = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    return r.stdout.strip().splitlines()[-1]


def test_compile_cache_goes_where_the_environment_says(tmp_path):
    outside = tmp_path / "cache"
    before = (set(os.listdir(DEFAULT_DIR)) if os.path.isdir(DEFAULT_DIR)
              else set())
    assert _probe_cache(str(outside)) == str(outside)
    assert os.listdir(outside), "no cache entry written"
    after = (set(os.listdir(DEFAULT_DIR)) if os.path.isdir(DEFAULT_DIR)
             else set())
    assert after == before, "the checkout's cache was written too"


def test_compile_cache_defaults_to_a_fixed_ignored_path():
    assert _probe_cache(None) == DEFAULT_DIR
    assert DEFAULT_DIR == os.path.join(REPO, ".jax_cache")
    assert os.listdir(DEFAULT_DIR)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_dryrun_peaks_by_device_kind():
    from repro.launch import dryrun
    pk = dryrun.peaks("TPU v5 lite")
    assert pk["bf16_flop_s"] == 197e12 and pk["hbm_byte_s"] == 819e9
    assert pk["ici_bit_s"] / 8 / pk["ici_links"] == 50e9
    with pytest.raises(KeyError, match="no published peaks"):
        dryrun.peaks("cpu")


def test_dryrun_import_leaves_xla_flags_alone():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["PYTHONPATH"] = SRC
    r = subprocess.run(
        [sys.executable, "-c", "import os, repro.launch.dryrun; "
         "print(os.environ.get('XLA_FLAGS'))"],
        env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == "None"
