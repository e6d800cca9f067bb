"""CPU tests of the chip benchmark at smoke sizes.

The plain references against the program's serve step, with a planted
fault; the harness end to end with the timed path sound and broken
underneath; the lower-precision control; the seeded generator; the cost
functions; the trace reduction; and the lookup of a new metric by name.
No test here reaches for a chip: ``run_cell(chip=False)`` skips that look.
"""
import dataclasses
import json
import os
import shutil
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.chip import compare, run, trace_reduce, traffic  # noqa: E402
from benchmarks.chip.drivers import serve_cohorts  # noqa: E402
from benchmarks.chip.reference import mamba2, olmo  # noqa: E402
from repro.configs import get_smoke  # noqa: E402
from repro.serving.engine import init_cache, make_serve_step  # noqa: E402

OLMO = {"num_hidden_layers": 2, "hidden_size": 96,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "intermediate_size": 192, "vocab_size": 512, "embedding_size": 512,
        "rope_theta": 10000.0}
MAMBA = {"num_hidden_layers": 2, "hidden_size": 64, "state_size": 16,
         "head_dim": 16, "expand": 2, "n_groups": 1, "conv_kernel": 4,
         "vocab_size": 512, "embedding_size": 512}
SMOKE = {
    "olmo-1b.even-cohort-decode": dict(
        model=OLMO, program_cfg=get_smoke("olmo-1b"),
        mix={"cohort_size": 8, "cache_len": 64,
             "lengths": {"dist": "lognormal", "median": 16, "sigma": 0.8,
                         "min": 2, "max": 48, "per": "cohort", "cycle": 4}}),
}
CELLS = sorted(SMOKE)
REFS = [(olmo, OLMO, "olmo-1b"), (mamba2, MAMBA, "mamba2-370m")]
SEED = 3_000_000_019            # over 2**31: seeds need more than 32 bits


def _decode(ref, dims, arch, steps=12, batch=4, broken=None):
    """Greedy decode through the program's serve step from one-token
    prompts; returns ``served`` as the harness keeps it."""
    cfg = get_smoke(arch)
    params = ref.init_params(dims, SEED)
    step = make_serve_step(cfg)
    if broken is not None:
        step = broken(step)
    step = jax.jit(step)
    cache = init_cache(cfg, steps + 1, batch)
    tok = np.random.default_rng(0).integers(0, dims["vocab_size"], batch)
    prompts, outs = tok.copy(), []
    for _ in range(steps):
        tok, cache = step(params, cache, jnp.asarray(tok, jnp.int32))
        tok = np.asarray(tok)
        outs.append(tok)
    outs = np.stack(outs, 1)
    served = {i: (prompts[i:i + 1], outs[i]) for i in range(batch)}
    return params, served


def _cache_unchanged(step):
    """The step returns its cache or state unchanged (writes dropped)."""
    def broken(params, cache, tokens):
        nxt, new = step(params, cache, tokens)
        return nxt, dataclasses.replace(
            new, k=cache.k, v=cache.v, ssm_state=cache.ssm_state,
            conv_tail=cache.conv_tail)
    return broken


def _token_altered(step):
    """One row's token is altered where the step produces it."""
    def broken(params, cache, tokens):
        nxt, new = step(params, cache, tokens)
        return nxt.at[0].set((nxt[0] + 1) % 512), new
    return broken


@pytest.mark.parametrize("ref,dims,arch", REFS,
                         ids=[a for _, _, a in REFS])
def test_reference_agrees_with_serve_step(ref, dims, arch):
    params, served = _decode(ref, dims, arch)
    gap, n = compare.widest_gap(ref, dims, params, served, sorted(served),
                                16)
    assert n == 48
    assert gap < 0.02, gap


@pytest.mark.parametrize("ref,dims,arch", REFS,
                         ids=[a for _, _, a in REFS])
def test_reference_catches_dropped_cache_write(ref, dims, arch):
    params, served = _decode(ref, dims, arch, broken=_cache_unchanged)
    gap, _ = compare.widest_gap(ref, dims, params, served, sorted(served),
                                16)
    assert gap > 0.2, gap


def _run(cell, trace=False, **kw):
    return run.run_cell(cell, SEED, 0.5, trace, chip=False,
                        overrides=SMOKE[cell], **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_on_cpu(cell):
    out = _run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    e2e = {m["name"] for m in run.cell_metrics(run.load_json(
        os.path.join(ROOT, "BENCHMARK.json")), cell, "end_to_end")}
    assert set(out["metrics"]) == e2e
    assert list(out)[-1] == "checks"
    assert out["tokens_compared"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_traced_cell_reports_host_metrics(cell):
    out = _run(cell, trace=True)
    assert out["correct"], out["checks"]
    # the CPU has no device plane: only the host's metrics can be read
    assert set(out["metrics"]) == {"serve.host_ms_per_round",
                                   "serve.wire_msgs_per_request"}
    assert out["metrics"]["serve.wire_msgs_per_request"]["value"] > 0


@pytest.mark.parametrize("fault", [_cache_unchanged, _token_altered],
                         ids=["state_unchanged", "token_altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    real = serve_cohorts.make_serve_step
    monkeypatch.setattr(serve_cohorts, "make_serve_step",
                        lambda cfg: fault(real(cfg)))
    out = _run(cell)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_int8_control_reads_far_above_the_program(cell):
    out = _run(cell, controls=("int8",))
    program = out["checks"]["logit_gap"]["value"]
    assert out["control_gap"]["int8"] > 3 * max(program, 1e-3), out


def test_generator_is_seeded_and_clipped():
    mix = traffic.load(os.path.join(HERE, "traffic",
                                    "even-cohort-decode.json"))

    def first(seed, n):
        gen = traffic.cohorts(mix, seed, 50304)
        return [next(gen) for _ in range(n)]

    a, b, c = first(SEED, 8), first(SEED, 8), first(SEED + 1, 8)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompts, y.prompts)
        assert x.max_new == y.max_new
    assert any(not np.array_equal(x.prompts, y.prompts)
               for x, y in zip(a, c))
    base = traffic.quantile_lengths(mix["lengths"], 4)
    assert base == [51, 99, 165, 321]
    for cyc in (a[:4], a[4:], c[:4]):
        assert sorted(co.max_new[0] for co in cyc) == base
        for co in cyc:
            assert len(set(co.max_new)) == 1
            assert co.prompts.shape == (64, 1)
            assert co.prompts.min() >= 0 and co.prompts.max() < 50304
    assert [co.max_new[0] for co in a[:4]] != \
        [co.max_new[0] for co in c[:4]] or not np.array_equal(
            a[0].prompts, c[0].prompts)
    per_req = dict(mix, lengths=dict(mix["lengths"], per="request"))
    for co in [next(traffic.cohorts(per_req, s, 50304)) for s in range(5)]:
        assert all(8 <= m <= 512 for m in co.max_new)


def _config(name):
    return run.load_json(os.path.join(HERE, "configs", name + ".json"))


def _bytes(shapes):
    return sum(int(np.prod(s)) * jnp.dtype(d).itemsize
               for s, d in jax.tree_util.tree_leaves(
                   shapes, is_leaf=lambda t: isinstance(t, tuple)
                   and isinstance(t[0], tuple)))


def test_olmo_costs_by_hand():
    m = _config("olmo-1b")["model"]
    # per layer 4*2048^2 + 3*2048*8192 = 67,108,864 weights; 16 layers
    # plus the 50304 x 2048 tied head: 1,176,764,416 -> 2.35 GFLOP a token
    flops, nbytes = olmo.decode_cost(m, [0])
    assert flops == 2 * 1_176_764_416 + 16 * 4 * 2048
    assert nbytes == 2 * 1_176_764_416 + 2 * 2048 + 16 * 2 * 2048 * 2
    assert _bytes(olmo.param_shapes(m)) == 2 * 1_176_764_416 + 2 * 2048
    f2, b2 = olmo.decode_cost(m, [10, 20])
    assert f2 == 2 * (2 * 1_176_764_416) + 16 * 4 * 2048 * (11 + 21)
    assert b2 == 2 * 1_176_764_416 + 2 * 2048 + 16 * 2 * 2048 * 2 * (11 + 21)


def test_mamba2_costs_by_hand():
    m = {"num_hidden_layers": 48, "hidden_size": 1024, "state_size": 128,
         "head_dim": 64, "expand": 2, "n_groups": 1, "conv_kernel": 4,
         "vocab_size": 50280, "embedding_size": 50304}
    # per layer 1024*(2*2048 + 32 + 2*128) + 2048*1024 = 6,586,368 matmul
    # weights; 48 layers plus the 50304 x 1024 head: 367,656,960
    flops, nbytes = mamba2.decode_cost(m, [0, 5, 9])
    assert flops == 3 * (2 * 367_656_960 + 48 * 5 * 32 * 128 * 64)
    state = 48 * (2 * 32 * 128 * 64 * 4 + 2 * 3 * 2048 * 2)
    assert nbytes == _bytes(mamba2.param_shapes(m)) + 3 * state
    assert _bytes(mamba2.param_shapes(m)) == 2 * 367_656_960 + 1_101_824


def test_trace_reduction_on_intervals():
    merged = trace_reduce.union([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)])
    assert merged == [(0.0, 2.0), (3.0, 4.0)]
    assert trace_reduce.gaps(merged, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0),
                                                    (4.0, 5.0)]
    spans = [("bench.round", 1.5, 4.5), ("bench.poll", 2.2, 2.9)]
    assert trace_reduce.attribute((2.0, 3.0), spans) == "bench.poll"
    assert trace_reduce.attribute((4.0, 5.0), spans) == "bench.round"
    assert trace_reduce.attribute((-1.0, 0.0), spans) == "no span"


def test_new_metric_file_is_found_by_name(tmp_path):
    """A per-layer metric is a new file and a new entry; no file that is
    already there changes."""
    copy = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, copy, ignore=shutil.ignore_patterns(
        "__pycache__", "test_*"))
    (copy / "metrics" / "serve.dummy_rounds.py").write_text(
        "def read(run):\n    return float(run.layer['rounds'])\n")
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = CELLS[0]
    bench["per_layer"].append({
        "name": "serve.dummy_rounds", "unit": "rounds", "better": "lower",
        "source": "host_clock", "layer": "scheduler",
        "moves": "decode_tokens_per_s", "workloads": [cell]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    saved = list(sys.path)
    try:
        mod = run.load_file(str(copy / "run.py"), "bench_run_copy")
    finally:
        sys.path[:] = saved
    names = [m["name"] for m in mod.cell_metrics(
        mod.load_json(str(tmp_path / "BENCHMARK.json")), cell, "per_layer")]
    assert "serve.dummy_rounds" in names
    assert "serve.dummy_rounds" not in [
        m["name"] for m in mod.cell_metrics(bench, "other.cell", "per_layer")]
    layer = type("Run", (), {"layer": {"rounds": 7}})()
    assert mod.read_metric("serve.dummy_rounds", layer) == 7.0


def test_trace_reduction_on_a_recorded_trace():
    """Four rounds of mamba2-370m serving 16 rows traced on a TPU v5e
    (``testdata/serve_4_rounds.xplane.pb``)."""
    pd = trace_reduce.load(os.path.join(HERE, "testdata"))
    s = trace_reduce.summarize(pd, stretch_span="bench.round",
                               module_match="serve_step")
    assert s["module_count"] == 4
    assert s["window_s"] == pytest.approx(0.040031, rel=1e-4)
    assert s["busy_s"] == pytest.approx(0.030691239, rel=1e-4)
    assert s["idle_share"] == pytest.approx(1 - s["busy_s"] / s["window_s"])
    assert s["module_s"] == pytest.approx(0.007674024, rel=1e-4)
    assert 4 * s["module_s"] == pytest.approx(s["busy_s"], rel=1e-3)
    ops = dict(s["device_ops"])
    assert set(ops) >= {"fusion.112", "constant_dynamic-slice_fusion.5"}
    assert not any(" = " in n or n.startswith("while") for n in ops)
    assert sum(ops.values()) <= s["busy_s"]
    gaps = s["idle_gaps"]
    # the device waits ~2 ms at the start of each step while the host
    # dispatches it
    assert [g[0] for g in gaps[:4]] == ["bench.serve_step"] * 4
    assert gaps[0][1] == pytest.approx(0.002193393, rel=1e-4)
    assert all(g[0].startswith("bench.") for g in gaps)
