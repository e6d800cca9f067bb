"""CPU tests of the Moonlight-16B-A3B cell at smoke sizes, with the
helpers of ``test_bench_cpu.py``.

The plain reference (expanded latent attention, the router's bias only
choosing) against the program's serve step (absorbed latent attention,
dropless held experts) on a share of 4 of 8 experts, with planted
faults; the int8 control; the cell end to end through ``run_cell``; the
cost function by hand at the cell's widths; the step compiled for a
described v5e at the cell's sizes.  The program runs in f32 where the
comparison reads the algebra (the weights are the reference's bf16
values either way); the cell's own run keeps the configuration's bf16.
"""
import dataclasses
import os
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

from benchmarks.chip import compare, run, test_bench_cpu as tbc  # noqa: E402
from benchmarks.chip.drivers import serve_cohorts  # noqa: E402
from benchmarks.chip.drivers import serve_cohorts_scoped  # noqa: E402
from benchmarks.chip.reference import moonlight, weights  # noqa: E402
from benchmarks.chip.test_bench_cpu import (SEED, _bytes, _config,  # noqa: E402
                                            _token_altered)
from benchmarks.chip.test_bench_fit import one_chip  # noqa: E402,F401
from benchmarks.chip.test_bench_fit import \
    test_serve_step_fits_one_v5e as _fits_one_v5e  # noqa: E402
from repro.configs import get_config, get_smoke  # noqa: E402
from repro.models.registry import build_model  # noqa: E402

CELL = "moonlight-16b-a3b.even-cohort-decode-b256"
MOON = {"num_hidden_layers": 3, "first_k_dense_replace": 1,
        "hidden_size": 96, "num_attention_heads": 4, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "router_n_experts": 8, "n_routed_experts": 4,
        "first_held_expert": 2, "num_experts_per_tok": 2,
        "n_shared_experts": 2, "routed_scaling_factor": 2.446,
        "rms_norm_eps": 1e-5, "rope_theta": 50000, "vocab_size": 512}
SHARE = dataclasses.replace(get_smoke("moonshot-v1-16b-a3b"),
                            experts_held=4, first_expert=2)
SMOKE_MIX = {"cohort_size": 8, "cache_len": 64, "trace_rounds": 4,
             "lengths": {"dist": "lognormal", "median": 16, "sigma": 0.6,
                         "min": 2, "max": 48, "per": "cohort", "cycle": 4}}


def _latent_unchanged(step):
    """The step returns its latent cache unchanged (rows not written)."""
    def broken(params, cache, tokens):
        nxt, new = step(params, cache, tokens)
        return nxt, dataclasses.replace(new, latent=cache.latent)
    return broken


def _f32_decode(monkeypatch, broken=None):
    """``test_bench_cpu._decode`` on the f32 share, fed the reference's
    bf16 weights as f32."""
    monkeypatch.setattr(tbc, "get_smoke", lambda arch: dataclasses.replace(
        SHARE, dtype=jnp.float32))
    made = moonlight.init_params
    monkeypatch.setattr(moonlight, "init_params", lambda m, s: (
        jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), made(m, s))))
    return tbc._decode(moonlight, MOON, "moonlight", broken=broken)


def _gap(params, served, control=None):
    return compare.widest_gap(moonlight, MOON, params, served,
                              sorted(served), 16, control=control)


def test_program_holds_the_reference_layout():
    run.check_program(SHARE, moonlight.program_fields(MOON))
    weights.check_layout(moonlight.param_shapes(MOON),
                         build_model(SHARE).abstract_params()[0])


def test_reference_agrees_with_serve_step(monkeypatch):
    params, served = _f32_decode(monkeypatch)
    gap, n = _gap(params, served)
    assert n == 48
    assert gap < 1e-3, gap           # f32 on both sides: the algebra only


@pytest.mark.parametrize("fault", [_latent_unchanged, _token_altered],
                         ids=["latent_row_dropped", "token_altered"])
def test_reference_catches_a_planted_fault(fault, monkeypatch):
    params, served = _f32_decode(monkeypatch, broken=fault)
    gap, _ = _gap(params, served)
    assert gap > 0.2, gap


def test_int8_control_reads_far_above_the_program(monkeypatch):
    params, served = _f32_decode(monkeypatch)
    program, _ = _gap(params, served)
    control, _ = _gap(params, served, control="int8")
    assert control > 3 * max(program, 1e-3), (control, program)


@pytest.fixture
def smoke_reference(monkeypatch):
    """The driver's share check finds the smoke dims, which no
    configuration file holds."""
    monkeypatch.setattr(serve_cohorts_scoped, "reference",
                        lambda cfg: (moonlight, MOON))


def _run(trace=False, **kw):
    return run.run_cell(CELL, SEED, 0.5, trace, chip=False,
                        overrides={"model": MOON, "program_cfg": SHARE,
                                   "mix": SMOKE_MIX}, **kw)


def test_driver_finds_the_cell_reference():
    ref, dims = serve_cohorts_scoped.reference(
        get_config("moonlight-16b-a3b-ep8"))
    assert ref is moonlight
    assert dims == _config("moonlight-16b-a3b")["model"]


def test_cell_runs_correct_on_cpu(smoke_reference):
    out = _run()
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"decode_tokens_per_s",
                                   "request_p95_ms", "setup_s"}
    assert out["tokens_compared"] > 0


def test_traced_cell_reads_the_expert_load_counter(smoke_reference):
    out = _run(trace=True)
    assert out["correct"], out["checks"]
    # the CPU has no device plane: the program's counter alone is read
    assert set(out["metrics"]) == {"moe_serve.held_expert_load_max_over_mean"}
    assert out["metrics"]["moe_serve.held_expert_load_max_over_mean"][
        "value"] >= 1.0
    assert out["checks"]["held_tokens_dropped"] == {"value": 0, "limit": 0}


def test_broken_timed_path_is_not_correct(smoke_reference, monkeypatch):
    real = serve_cohorts.make_serve_step
    monkeypatch.setattr(serve_cohorts, "make_serve_step",
                        lambda cfg: _latent_unchanged(real(cfg)))
    out = _run()
    assert not out["correct"]
    # the share separates here; the widest gap bounds gross faults only
    share = out["checks"]["logit_gap_share"]
    assert share["value"] > share["limit"]


def test_moonlight_costs_by_hand():
    m = _config("moonlight-16b-a3b")["model"]
    # per layer MLA 2048*3072 + 2048*576 + 512*2048 + 512*2048
    # + 2048*2048 = 13,762,560; dense FFN 3*2048*11264 = 69,206,016;
    # per MoE layer shared 3*2048*2816 = 17,301,504, router 2048*64 =
    # 131,072, one expert 3*2048*1408 = 8,650,752; head 163840*2048
    mla, dense, shared, router, expert = (13_762_560, 69_206_016,
                                          17_301_504, 131_072, 8_650_752)
    head = 163_840 * 2048
    per_row = (27 * mla + dense + 26 * (shared + router) + head)
    routed = 26 * expert * 6 * 8 / 64          # 0.75 expert a row a layer
    touched = 8 * (1 - (1 - 6 / 64) ** 256)
    attn = 27 * 2 * 16 * (2 * 512 + 64)
    norms = 27 * (2 * 2048 + 512) + 2048
    flops, nbytes = moonlight.decode_cost(m, [0] * 256)
    assert flops == pytest.approx(256 * (2 * (per_row + routed) + attn),
                                  rel=1e-12)
    lat = 256 * 27 * 576 * 2
    want = (2 * (per_row + 26 * touched * expert + norms + 256 * 2048)
            + 4 * 26 * 64 + lat)
    assert nbytes == pytest.approx(want, rel=1e-12)
    # every held expert is read: the weights, less the embedding, are
    # 6.06 GB; the whole parameter set is 6.73 GB
    assert 6.05e9 < nbytes - lat < 6.07e9
    assert _bytes(moonlight.param_shapes(m)) == pytest.approx(6.73e9,
                                                              rel=1e-3)
    f2, b2 = moonlight.decode_cost(m, [10, 20])
    assert b2 - moonlight.decode_cost(m, [0, 0])[1] == 27 * 576 * 2 * 30
    assert f2 - moonlight.decode_cost(m, [0, 0])[0] == attn * 30


def test_moonlight_step_fits_one_v5e(one_chip):
    _fits_one_v5e(CELL, one_chip)
