"""Compile the main path's kernels and the olmo-1b and Moonlight serve
steps for a described TPU v5e chip, at real widths; at the benchmark
cells' shapes the olmo-1b step moves no whole K/V buffer and the
Moonlight step reads its latent cache only inside the latent decode
kernel.

Nothing runs: ``lower(...).compile()`` against a ``v5e:2x2`` topology
raises what the chip's compiler would raise (tile-misaligned blocks,
scoped-VMEM overflow, a program that does not fit HBM).  The topology is
described inside a module fixture, never at import time, because only
one process at a time may load the TPU compiler library.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.doorbell.ops import stage_copy
from repro.kernels.flash_attention.kernel import flash_attention_tpu
from repro.kernels.latent_decode.kernel import latent_decode_tpu
from repro.kernels.moe_gmm.kernel import moe_gmm_tpu
from repro.kernels.rmsnorm.kernel import rmsnorm_tpu
from repro.kernels.ssd_scan.kernel import ssd_scan_tpu
from repro.models.registry import build_model
from repro.serving.engine import init_cache, make_serve_step

HBM_BYTES = 16 * 2**30          # one v5e chip
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_HEADER = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_INSTR = re.compile(r"^\s*(ROOT )?%([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")
_ARRAY = re.compile(r"\w+\[([\d,]*)\]")
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")
#: ops that name or pass on a buffer without writing one
_VIEWS = {"parameter", "get-tuple-element", "tuple", "bitcast", "while"}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler library / plug-in here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip; keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(fn, *args):
    """Compile for the described chip; the Pallas kernel must be in the
    program as a Mosaic custom call, not interpreted."""
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_flash_attention_olmo_1b(one_chip):
    cfg = get_config("olmo-1b")
    dh = cfg.d_model // cfg.n_heads
    q = _sds((1, cfg.n_heads, 2048, dh), jnp.bfloat16, one_chip)
    _compile_kernel(lambda q, k, v: flash_attention_tpu(q, k, v),
                    q, q, q)


def test_latent_decode_moonlight_cell(one_chip):
    """At the Moonlight cell's shapes: 27 layers x 256 rows x 512
    positions x 640, bf16, and the f32 query of 16 heads."""
    _compile_kernel(lambda q, lat, i, n: latent_decode_tpu(q, lat, i, n,
                                                           rank=512),
                    _sds((256, 16, 640), jnp.float32, one_chip),
                    _sds((27, 256, 512, 640), jnp.bfloat16, one_chip),
                    _sds((), jnp.int32, one_chip),
                    _sds((), jnp.int32, one_chip))


def test_rmsnorm_d2048(one_chip):
    _compile_kernel(rmsnorm_tpu, _sds((4096, 2048), jnp.bfloat16, one_chip),
                    _sds((2048,), jnp.bfloat16, one_chip))


@pytest.mark.parametrize("wire_bf16,e", [(True, 256), (False, 16)])
def test_doorbell_stage_copy(one_chip, wire_bf16, e):
    _compile_kernel(lambda x: stage_copy(x, wire_bf16=wire_bf16),
                    _sds((64, e), jnp.float32, one_chip))


def test_moe_gmm_olmoe_1b_7b(one_chip):
    cfg = get_config("olmoe-1b-7b")
    e, cap, d, f = cfg.n_experts, 128, cfg.d_model, cfg.d_ff
    _compile_kernel(lambda x, w1, w2: moe_gmm_tpu(x, w1, w2, act="swiglu"),
                    _sds((e, cap, d), jnp.bfloat16, one_chip),
                    _sds((e, d, 2 * f), jnp.bfloat16, one_chip),
                    _sds((e, f, d), jnp.bfloat16, one_chip))


def test_ssd_scan_mamba2_370m(one_chip):
    cfg = get_config("mamba2-370m")
    h, p, n = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    bs, s, g = 1, 2048, cfg.ssm_groups
    f32 = jnp.float32
    bf16 = jnp.bfloat16
    _compile_kernel(lambda x, dt, a, b, c, d: ssd_scan_tpu(
                        x, dt, a, b, c, d, chunk=cfg.ssm_chunk),
                    _sds((bs, h, s, p), bf16, one_chip),
                    _sds((bs, h, s), bf16, one_chip),
                    _sds((h,), f32, one_chip),
                    _sds((bs, g, s, n), bf16, one_chip),
                    _sds((bs, g, s, n), bf16, one_chip),
                    _sds((h,), f32, one_chip))


def test_olmo_1b_serve_step_fits_one_chip(one_chip):
    cfg = get_config("olmo-1b")
    params, _ = build_model(cfg).abstract_params()
    cache = jax.eval_shape(lambda: init_cache(cfg, 2048, 16))
    on_chip = lambda t: jax.tree_util.tree_map(          # noqa: E731
        lambda a: _sds(a.shape, a.dtype, one_chip), t)
    compiled = jax.jit(make_serve_step(cfg), donate_argnums=(1,)).lower(
        on_chip(params), on_chip(cache),
        _sds((16,), jnp.int32, one_chip)).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, f"{used / 2**30:.2f} GiB on a 16 GiB chip"


def _kernels(hlo: str):
    """Each instruction outside a fused computation, as (name, opcode,
    output dims, the opcode of its fused computation's root or None)."""
    comps, roots, comp = {}, {}, None
    for line in hlo.splitlines():
        head = _HEADER.match(line)
        if head:
            comp = head.group(1)
            comps[comp] = []
            continue
        m = _INSTR.match(line)
        if m and comp is not None:
            calls = _CALLS.search(line)
            comps[comp].append((m.group(2), m.group(4),
                                [tuple(int(d) for d in a.split(",") if d)
                                 for a in _ARRAY.findall(m.group(3))],
                                calls.group(1) if calls else None))
            if m.group(1):
                roots[comp] = m.group(4)
    fused = {c for ops in comps.values() for _, op, _, c in ops
             if op == "fusion"}
    return [(name, op, dims, roots.get(c) if op == "fusion" else None)
            for comp, ops in comps.items() if comp not in fused
            for name, op, dims, c in ops]


def test_olmo_1b_serve_step_writes_one_kv_row_in_place(one_chip):
    """At the benchmark cell's shapes (64 rows, cache 512, donated) the
    step writes each layer's K/V row into the cache in place and reads
    the layer inside attention: no copy, scatter or slice of a whole
    cache or a whole layer, and next to no temporary memory."""
    cfg = get_config("olmo-1b")
    params, _ = build_model(cfg).abstract_params()
    cache = jax.eval_shape(lambda: init_cache(cfg, 512, 64))
    on_chip = lambda t: jax.tree_util.tree_map(          # noqa: E731
        lambda a: _sds(a.shape, a.dtype, one_chip), t)
    compiled = jax.jit(make_serve_step(cfg), donate_argnums=(1,)).lower(
        on_chip(params), on_chip(cache),
        _sds((64,), jnp.int32, one_chip)).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 64 * 2**20, f"{temp / 2**30:.2f} GiB of temporaries"
    # shapes up to order and unit dims: the layout may put them anywhere
    whole = sorted(cache.k.shape)
    layer = sorted(cache.k.shape[1:])
    writes = []
    for name, op, dims, root in _kernels(compiled.as_text()):
        for d in dims:
            d = sorted(x for x in d if x != 1)
            if d == whole and op not in _VIEWS:
                assert op == "dynamic-update-slice" or (
                    op == "fusion" and root == "dynamic-update-slice"), (
                    name, op, root)
                writes.append(name)
            assert d != layer or op in _VIEWS, (name, op, root)
    assert len(writes) >= 2, writes          # K and V, in the layer loop


def test_moonlight_serve_step_reads_latent_rows_in_the_kernel(one_chip):
    """At the benchmark cell's shapes (256 rows, cache 512, donated) the
    only op that reads the latent cache's rows is the latent decode
    kernel, under the ``mla_attn`` scope that
    ``moe_serve.mla_attn_ms_per_step`` reads; no op copies out a layer,
    and only the rows' write gives a whole cache."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmarks.chip.program_trace import in_scope, op_scopes
    cfg = get_config("moonlight-16b-a3b-ep8")
    params, _ = build_model(cfg).abstract_params()
    cache = jax.eval_shape(lambda: init_cache(cfg, 512, 256))
    on_chip = lambda t: jax.tree_util.tree_map(          # noqa: E731
        lambda a: _sds(a.shape, a.dtype, one_chip), t)
    hlo = jax.jit(make_serve_step(cfg), donate_argnums=(1,)).lower(
        on_chip(params), on_chip(cache),
        _sds((256,), jnp.int32, one_chip)).compile().as_text()
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2, calls        # the dense layer and the loop's
    assert all(" %latent_decode" in c and "bf16[27,256,512,640]" in c
               for c in calls), calls
    scopes = op_scopes(hlo)
    names = [c.split("=")[0].strip().lstrip("%") for c in calls]
    assert all(in_scope(scopes.get(n), "mla_attn") for n in names), (
        [scopes.get(n) for n in names])
    whole = sorted(cache.latent.shape)
    for name, op, dims, root in _kernels(hlo):
        for d in dims:
            d = sorted(x for x in d if x != 1)
            if d == whole and op not in _VIEWS:
                assert op == "dynamic-update-slice" or (
                    op == "fusion" and root == "dynamic-update-slice"), (
                    name, op, root)
            # a layer's rows, whole or their latent part, in any dtype
            assert d not in (sorted((256, 512, 640)),
                             sorted((256, 512, 512))), (name, op, root)
