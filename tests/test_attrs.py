"""The unified attribute system (DESIGN.md §12).

Covers the four-layer resolution chain (defaults → REPRO_ATTR_* env →
runtime config → per-resource overrides) as a hypothesis property, the
``get_attr``/``attrs`` surface on every resource type, alloc-time
validation errors that name the attribute, the CommConfig/EndpointSpec
deprecation shims, and — in a subprocess — that an env override really
changes protocol selection.
"""
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

import repro.core as C
from repro.core import attrs as A

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture(autouse=True)
def _no_ambient_attr_env(monkeypatch):
    """These tests assert exact layer outcomes; ambient REPRO_ATTR_*
    (e.g. the CI attr-override smoke leg) must not leak in."""
    for key in list(os.environ):
        if key.startswith(A.ENV_PREFIX):
            monkeypatch.delenv(key, raising=False)


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_every_tunable_has_type_default_mutability(self):
        assert A.REGISTRY, "registry must not be empty"
        for name, spec in A.REGISTRY.items():
            assert spec.name == name
            assert spec.type in (int, float, bool, str, dict)
            assert spec.mutability in ("alloc", "env", "readonly")
            if spec.mutability != "readonly":
                # defaults must validate against their own spec
                assert spec.validate(spec.default) == spec.default

    def test_core_knobs_registered(self):
        for name in ("eager_max_bytes", "rdv_threshold", "packets_per_lane",
                     "packet_bytes", "pool_lanes", "backlog_capacity",
                     "cq_capacity", "worker_burst", "n_workers", "stripe",
                     "progress", "n_devices", "fabric_depth", "link_latency",
                     "matching_buckets", "lock_spin_count"):
            assert name in A.REGISTRY, name

    def test_registry_table_renders_every_attr(self):
        table = A.registry_table()
        for name in A.REGISTRY:
            assert f"`{name}`" in table

    def test_unknown_name_error_lists_known(self):
        with pytest.raises(ValueError, match="unknown attribute"):
            A.get_spec("rdv_treshold")           # typo

    def test_env_var_spelling(self):
        assert A.get_spec("rdv_threshold").env_var == \
            "REPRO_ATTR_RDV_THRESHOLD"


# ---------------------------------------------------------------------------
# the resolution chain
# ---------------------------------------------------------------------------

class TestResolutionChain:
    def test_default_layer(self):
        r = A.resolve(["rdv_threshold"], env={})
        assert r["rdv_threshold"] == 2 * 1024 * 1024
        assert r.source("rdv_threshold") == "default"

    def test_env_beats_default(self):
        r = A.resolve(["rdv_threshold"],
                      env={"REPRO_ATTR_RDV_THRESHOLD": "4096"})
        assert r["rdv_threshold"] == 4096
        assert r.source("rdv_threshold") == "env"

    def test_runtime_beats_env(self):
        r = A.resolve(["rdv_threshold"], runtime={"rdv_threshold": 512},
                      env={"REPRO_ATTR_RDV_THRESHOLD": "4096"})
        assert r["rdv_threshold"] == 512
        assert r.source("rdv_threshold") == "runtime"

    def test_resource_beats_runtime(self):
        r = A.resolve(["rdv_threshold"], runtime={"rdv_threshold": 512},
                      overrides={"rdv_threshold": 64},
                      env={"REPRO_ATTR_RDV_THRESHOLD": "4096"})
        assert r["rdv_threshold"] == 64
        assert r.source("rdv_threshold") == "resource"

    @given(st.booleans(), st.booleans(), st.booleans(),
           st.integers(min_value=1, max_value=1 << 20),
           st.integers(min_value=1, max_value=1 << 20),
           st.integers(min_value=1, max_value=1 << 20))
    @settings(max_examples=60, deadline=None)
    def test_property_highest_present_layer_wins(self, has_env, has_rt,
                                                 has_over, v_env, v_rt,
                                                 v_over):
        """Per-resource overrides beat runtime config beat REPRO_ATTR_*
        env beats library defaults — for every presence combination."""
        env = ({"REPRO_ATTR_EAGER_MAX_BYTES": str(v_env)}
               if has_env else {})
        rt = {"eager_max_bytes": v_rt} if has_rt else {}
        over = {"eager_max_bytes": v_over} if has_over else {}
        r = A.resolve(["eager_max_bytes"], runtime=rt, overrides=over,
                      env=env)
        if has_over:
            expect, source = v_over, "resource"
        elif has_rt:
            expect, source = v_rt, "runtime"
        elif has_env:
            expect, source = v_env, "env"
        else:
            expect, source = A.get_spec("eager_max_bytes").default, "default"
        assert r["eager_max_bytes"] == expect
        assert r.source("eager_max_bytes") == source

    def test_full_chain_through_alloc_cq(self, monkeypatch):
        monkeypatch.setenv("REPRO_ATTR_CQ_CAPACITY", "5")
        assert C.LocalCluster(1)[0].alloc_cq().capacity == 5
        cl = C.LocalCluster(1, attrs={"cq_capacity": 7})
        assert cl[0].alloc_cq().capacity == 7
        cq = cl[0].alloc_cq(capacity=9)
        assert cq.capacity == 9
        assert cq.attr_source("cq_capacity") == "resource"

    def test_env_override_reaches_config(self, monkeypatch):
        monkeypatch.setenv("REPRO_ATTR_EAGER_MAX_BYTES", "16")
        cl = C.LocalCluster(1)
        assert cl.config.inject_max_bytes == 16
        assert cl[0].get_attr("eager_max_bytes") == 16
        assert cl[0].attr_source("eager_max_bytes") == "env"

    def test_cluster_attrs_beat_explicit_config_fields(self):
        cl = C.LocalCluster(1, C.CommConfig(inject_max_bytes=128),
                            attrs={"eager_max_bytes": 32})
        assert cl.config.inject_max_bytes == 32

    def test_spec_path_honors_runtime_layer(self):
        """alloc_endpoint(spec=...) re-resolves the spec's non-explicit
        fields through the cluster's attrs layer; fields the spec's
        caller pinned stay pinned."""
        cl = C.LocalCluster(1, attrs={"stripe": "by_peer"})
        ambient = cl[0].alloc_endpoint(spec=C.EndpointSpec(name="a"))
        assert ambient.spec.stripe == "by_peer"
        pinned = cl[0].alloc_endpoint(
            spec=C.EndpointSpec(name="p", stripe="round_robin"))
        assert pinned.spec.stripe == "round_robin"

    def test_collapsed_device_width_agrees_with_introspection(self):
        """BSP collapses channels to 1; the stored resolution must say
        so (what the device runs with, not the raw knob)."""
        cl = C.LocalCluster(1, attrs={"mode": "bsp", "n_channels": 4})
        dev = cl[0].default_device
        assert dev.get_attr("n_channels") == dev.get_attr("width") == 1

    def test_echo_block_shape(self):
        echo = C.LocalCluster(1, attrs={"rdv_threshold": 4096}).attrs_echo()
        assert set(echo) == {"values", "sources"}
        assert echo["values"]["rdv_threshold"] == 4096
        assert echo["sources"]["rdv_threshold"] == "runtime"
        assert echo["sources"]["rank_n"] == "discovered"
        import json
        json.dumps(echo)                        # must be JSON-serializable


# ---------------------------------------------------------------------------
# get_attr on every resource type
# ---------------------------------------------------------------------------

class TestEveryResourceQueryable:
    def test_all_eight_resource_types(self):
        cl = C.LocalCluster(2, attrs={"rdv_threshold": 4096})
        rt = cl[0]
        # 1. cluster
        assert cl.get_attr("fabric_depth") == 4096
        assert cl.get_attr("rank_n") == 2
        # 2. runtime
        assert rt.get_attr("rdv_threshold") == 4096
        assert rt.get_attr("rank_me") == 0
        assert rt.get_attr("free_packets") > 0
        # 3. device
        dev = rt.default_device
        assert dev.get_attr("width") == dev.n_channels
        assert dev.get_attr("backlog_capacity") == 0
        # 4. endpoint
        ep = rt.alloc_endpoint(2, "by_peer", name="q")
        assert ep.get_attr("stripe") == "by_peer"
        assert ep.get_attr("width") == 2
        assert "contentions" in ep.get_attr("contention")
        # 5. packet pool
        pool = rt.packet_pool
        assert pool.get_attr("packets_per_lane") == \
            rt.get_attr("packets_per_lane")
        assert pool.get_attr("free_packets") == pool.free_packets()
        # 6. matching engine
        assert rt.matching.get_attr("matching_buckets") == 65536
        assert rt.matching.get_attr("inserts") == 0
        # 7. completion objects — all five kinds
        assert rt.alloc_cq(capacity=3).get_attr("cq_capacity") == 3
        assert rt.alloc_cq(threadsafe=True).get_attr("threadsafe") is True
        assert rt.alloc_sync(expected=2).get_attr("expected") == 2
        h = rt.alloc_handler(lambda st: None)
        assert h.get_attr("signals") == 0
        g = rt.alloc_graph("g")
        assert g.get_attr("n_nodes") == 0
        # 8. worker pool + fabric
        pool8 = rt.alloc_workers(2, burst=16)
        assert pool8.get_attr("worker_burst") == 16
        assert pool8.get_attr("n_workers") == 2
        assert cl.fabric.get_attr("fabric_depth") == 4096
        assert cl.fabric.get_attr("in_flight") == 0

    def test_attrs_snapshot_includes_discovered(self):
        rt = C.LocalCluster(1)[0]
        snap = rt.attrs
        assert snap["rank_me"] == 0
        assert "rdv_threshold" in snap

    def test_unknown_attr_names_resource_and_lists_available(self):
        rt = C.LocalCluster(1)[0]
        with pytest.raises(ValueError, match="Runtime.*no attribute"):
            rt.get_attr("does_not_exist")


# ---------------------------------------------------------------------------
# alloc-time validation (satellite: clear ValueErrors naming the attr)
# ---------------------------------------------------------------------------

class TestAllocValidation:
    def test_unknown_stripe_policy(self):
        with pytest.raises(ValueError, match="'stripe'.*hash"):
            C.EndpointSpec(stripe="hash")

    def test_unknown_progress_policy(self):
        with pytest.raises(ValueError, match="'progress'"):
            C.EndpointSpec(progress="thread")

    def test_nonpositive_devices(self):
        with pytest.raises(ValueError, match="'n_devices'"):
            C.EndpointSpec(n_devices=0)

    def test_negative_workers(self):
        with pytest.raises(ValueError, match="'n_workers'"):
            C.EndpointSpec(progress="workers", n_workers=-1)

    def test_worker_pool_rejects_nonpositive_workers(self):
        rt = C.LocalCluster(1)[0]
        with pytest.raises(ValueError, match="'n_workers'"):
            C.ProgressWorkerPool([(rt.engine, rt.default_device)],
                                 n_workers=0)

    def test_negative_capacity(self):
        rt = C.LocalCluster(1)[0]
        with pytest.raises(ValueError, match="'cq_capacity'"):
            rt.alloc_cq(capacity=-1)
        with pytest.raises(ValueError, match="'backlog_capacity'"):
            rt.alloc_device(backlog_capacity=-2)

    def test_negative_size_boundary(self):
        with pytest.raises(ValueError, match="'size_boundaries'"):
            C.EndpointSpec(n_devices=2, stripe="by_size",
                           size_boundaries=(-1, 64))

    def test_unknown_cluster_attr(self):
        with pytest.raises(ValueError, match="unknown attribute"):
            C.LocalCluster(1, attrs={"not_an_attr": 1})

    def test_unknown_alloc_override(self):
        rt = C.LocalCluster(1)[0]
        with pytest.raises(ValueError, match="unknown attribute override"):
            rt.alloc_device(stripe="by_peer")   # endpoint attr, not device

    def test_wrong_type(self):
        with pytest.raises(ValueError, match="'fabric_depth'.*int"):
            C.LocalCluster(1, attrs={"fabric_depth": "deep"})

    def test_explicit_workers_on_shared_endpoint_still_errors(self):
        with pytest.raises(ValueError, match="'n_workers'"):
            C.EndpointSpec(progress="shared", n_workers=3)

    def test_errors_are_fatal_errors_too(self):
        # the deprecation-shim contract: historical call sites catch
        # FatalError; AttrError must satisfy both spellings
        with pytest.raises(C.FatalError):
            C.EndpointSpec(stripe="hash")

    def test_readonly_attr_cannot_be_set(self):
        with pytest.raises(ValueError, match="read-only|readonly"):
            C.LocalCluster(1, attrs={"rank_n": 4})


# ---------------------------------------------------------------------------
# deprecation shims
# ---------------------------------------------------------------------------

class TestShims:
    def test_commconfig_old_kwargs_still_work(self):
        cfg = C.CommConfig(inject_max_bytes=256, bufcopy_max_bytes=1024)
        assert cfg.inject_max_bytes == 256
        assert cfg.bufcopy_max_bytes == 1024
        assert cfg.get_attr("eager_max_bytes") == 256
        assert cfg.get_attr("rdv_threshold") == 1024

    def test_commconfig_replace_roundtrip(self):
        import dataclasses
        cfg = dataclasses.replace(C.CommConfig(), n_channels=2)
        assert cfg.n_channels == 2
        assert cfg.resolved_channels() == 2

    def test_alias_spellings_resolve_with_warning(self):
        with pytest.warns(DeprecationWarning, match="inject_max_bytes"):
            cl = C.LocalCluster(1, attrs={"inject_max_bytes": 99})
        assert cl.config.inject_max_bytes == 99

    def test_get_attr_accepts_alias(self):
        cfg = C.CommConfig(bufcopy_max_bytes=2048)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            assert cfg.get_attr("bufcopy_max_bytes") == 2048

    def test_endpointspec_positional_compat(self):
        spec = C.EndpointSpec("ep", 2, "by_size", "dedicated")
        assert (spec.name, spec.n_devices, spec.stripe, spec.progress) == \
            ("ep", 2, "by_size", "dedicated")

    def test_spec_for_mode_roundtrip(self):
        spec = C.EndpointSpec.for_mode(C.CommMode.LCI_DEDICATED, 4)
        assert spec.progress == "dedicated" and spec.n_devices == 4


# ---------------------------------------------------------------------------
# env overrides really change behaviour (subprocess: fresh import + env)
# ---------------------------------------------------------------------------

_PROTO_SCRIPT = """
import numpy as np
import repro.core as C

cl = C.LocalCluster(2)
r0, r1 = cl[0], cl[1]
landed = []
h = r1.alloc_handler(landed.append)
buf = np.zeros(64, np.uint8)
C.post_recv_x(r1, 0, buf, 64, 7).local_comp(h)()
C.post_send_x(r0, 1, np.arange(64, dtype=np.uint8), 64, 7)()
for _ in range(10_000):
    if landed:
        break
    cl.progress_all()
assert landed, "message never delivered"
assert buf[13] == 13
s = r0.stats
print(f"inject={s.inject_msgs} bufcopy={s.bufcopy_msgs} "
      f"zerocopy={s.zerocopy_msgs} handshakes={s.handshakes} "
      f"rdv_threshold={r0.get_attr('rdv_threshold')}")
"""


def _run_proto_subprocess(extra_env):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(A.ENV_PREFIX)}
    env.update(extra_env)
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", _PROTO_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    return dict(kv.split("=") for kv in r.stdout.split())


class TestEnvOverrideSubprocess:
    def test_default_is_inject(self):
        out = _run_proto_subprocess({})
        assert out["inject"] == "1" and out["zerocopy"] == "0"
        assert out["rdv_threshold"] == str(2 * 1024 * 1024)

    def test_tiny_rdv_threshold_switches_to_rendezvous(self):
        # a 64-byte send with eager_max 8 / rdv_threshold 16 must take
        # the zero-copy rendezvous path (RTS/CTS handshake) — the env
        # layer really reaches protocol selection
        out = _run_proto_subprocess({
            "REPRO_ATTR_EAGER_MAX_BYTES": "8",
            "REPRO_ATTR_RDV_THRESHOLD": "16",
        })
        assert out["zerocopy"] == "1" and out["inject"] == "0"
        assert int(out["handshakes"]) >= 1
        assert out["rdv_threshold"] == "16"
