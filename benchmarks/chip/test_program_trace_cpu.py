"""CPU tests of the readers of the program's own instrumentation
(``program_trace.py``): the op-to-scope map on a CPU-compiled smoke step
and on a hand-written program with XLA's layout copies, the readers on
recorded traces with and without the program's spans, and the window
readings of a telemetry hub.  No test here reaches for a chip."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.profiler import ProfileData  # noqa: E402

from benchmarks.chip import program_trace, trace_reduce  # noqa: E402
from repro.configs import get_smoke  # noqa: E402
from repro.core.telemetry import Telemetry  # noqa: E402
from repro.models.registry import build_model  # noqa: E402
from repro.serving.engine import (CACHE_IO, init_cache,  # noqa: E402
                                  make_serve_step)

# The shape of the olmo-1b step as compiled for a v5e: the K cache enters
# as a parameter, is copied into the loop's layout, written in the loop
# under the scope, and copied back out.
STEP = """\
HloModule jit_serve_step, entry_computation_layout={(f32[4,8])->f32[4,8]}

%fused_computation (param_0: f32[4,8], param_1: s32[]) -> f32[4,8] {
  %param_0 = f32[4,8]{1,0} parameter(0)
  ROOT %dynamic-update-slice.1 = f32[4,8]{1,0} dynamic-update-slice(%param_0, %param_0), metadata={op_name="jit(serve_step)/while/body/closed_call/cache_io/scatter"}
}

%body (arg_tuple.0: (s32[], f32[4,8])) -> (s32[], f32[4,8]) {
  %arg_tuple.0 = (s32[], f32[4,8]{0,1:T(8,128)}) parameter(0)
  %get-tuple-element.1 = s32[] get-tuple-element(%arg_tuple.0), index=0
  %get-tuple-element.2 = f32[4,8]{0,1:T(8,128)} get-tuple-element(%arg_tuple.0), index=1
  %fusion.129 = f32[4,8]{0,1:T(8,128)} fusion(%get-tuple-element.2, %get-tuple-element.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(serve_step)/while/body/closed_call/cache_io/scatter" stack_frame_id=3}
  %add.1 = s32[] add(%get-tuple-element.1, %get-tuple-element.1), metadata={op_name="jit(serve_step)/while/body/add"}
  ROOT %tuple.2 = (s32[], f32[4,8]{0,1:T(8,128)}) tuple(%add.1, %fusion.129)
}

%cond (arg_tuple.1: (s32[], f32[4,8])) -> pred[] {
  %arg_tuple.1 = (s32[], f32[4,8]{0,1:T(8,128)}) parameter(0)
  ROOT %constant.2 = pred[] constant(false)
}

ENTRY %main (cache_0_.1: f32[4,8], other.1: f32[4,8]) -> (f32[4,8], f32[4,8]) {
  %cache_0_.1 = f32[4,8]{1,0} parameter(0), metadata={op_name="cache[0]"}
  %other.1 = f32[4,8]{1,0} parameter(1), metadata={op_name="other"}
  %constant.1 = s32[] constant(0)
  %copy.28 = f32[4,8]{0,1:T(8,128)} copy(%cache_0_.1)
  %tuple.1 = (s32[], f32[4,8]{0,1:T(8,128)}) tuple(%constant.1, %copy.28)
  %while.1 = (s32[], f32[4,8]{0,1:T(8,128)}) while(%tuple.1), condition=%cond, body=%body
  %get-tuple-element.3 = f32[4,8]{0,1:T(8,128)} get-tuple-element(%while.1), index=1
  %copy.33 = f32[4,8]{1,0} copy(%get-tuple-element.3)
  %copy.40 = f32[4,8]{0,1} copy(%other.1)
  %get-tuple-element.4 = s32[] get-tuple-element(%while.1), index=0
  %copy.41 = s32[] copy(%get-tuple-element.4)
  ROOT %tuple.3 = (f32[4,8]{1,0}, f32[4,8]{0,1}, s32[]) tuple(%copy.33, %copy.40, %copy.41)
}
"""


def test_copies_take_the_scope_of_what_they_copy():
    scopes = program_trace.op_scopes(STEP)
    write = "jit(serve_step)/while/body/closed_call/cache_io/scatter"
    assert scopes["fusion.129"] == write
    # out of the loop: the producer of the carried value, through the
    # loop's result tuple and its body's root
    assert scopes["copy.33"] == write
    # into the loop: a parameter takes the writer of its carry slot
    assert scopes["copy.28"] == write
    # another slot of the same loop takes that slot's writer
    assert scopes["copy.41"] == "jit(serve_step)/while/body/add"
    # a parameter's name is not a scope, and a copy of one that enters
    # no loop has none
    assert "cache_0_.1" not in scopes and "copy.40" not in scopes
    assert [k for k, v in scopes.items()
            if program_trace.in_scope(v, CACHE_IO)] == [
        "dynamic-update-slice.1", "fusion.129", "copy.28", "copy.33"]


def _smoke_hlo(arch):
    cfg = get_smoke(arch)
    params = build_model(cfg).abstract_params()[0]
    cache = jax.eval_shape(lambda: init_cache(cfg, 16, 4))
    return jax.jit(make_serve_step(cfg), donate_argnums=(1,)).lower(
        params, cache, jnp.zeros((4,), jnp.int32)).compile().as_text()


@pytest.mark.parametrize("arch", ["olmo-1b", "mamba2-370m"])
def test_op_scopes_on_a_cpu_smoke_step(arch):
    hlo = _smoke_hlo(arch)
    scopes = program_trace.op_scopes(hlo)
    own = {}
    for line in hlo.splitlines():
        if " = " in line and 'op_name="' in line and \
                " parameter(" not in line:
            name = line.split(" = ", 1)[0].split("%", 1)[1]
            own[name] = line.split('op_name="', 1)[1].split('"', 1)[0]
    # every op that names its scope keeps it
    assert own and all(scopes[k] == v for k, v in own.items())
    cache_io = [k for k, v in scopes.items()
                if program_trace.in_scope(v, CACHE_IO)]
    kinds = {k.split(".")[0].replace("_", "-") for k in cache_io}
    assert {"dynamic-slice", "dynamic-update-slice"} <= kinds, cache_io
    # the step's matmuls lie outside the scope
    dots = [k for k, v in own.items() if v.endswith("/dot_general")]
    assert dots and not any(program_trace.in_scope(scopes[k], CACHE_IO)
                            for k in dots)


def test_readers_find_nothing_in_a_trace_without_program_spans():
    """A program without the scope or the spans (as the recorded
    mamba2-370m trace is) gives empty readings and does not raise."""
    pd = ProfileData.from_file(
        os.path.join(HERE, "testdata", "serve_4_rounds.xplane.pb"))
    for scopes in ({}, {"fusion.112": "jit(serve_step)/while/body"}):
        assert program_trace.program_layers(
            pd, scopes, scope=CACHE_IO, gap_span="sched.decode",
            stretch_span="bench.round", module_match="serve_step") == {}
    assert program_trace.program_layers(
        pd, {"fusion.112": "jit(serve_step)/cache_io/x"}, scope=CACHE_IO,
        gap_span="sched.decode", stretch_span="bench.round",
        module_match="serve_step")["gap_idle_s"] == 0.0


def test_program_readers_on_a_recorded_trace():
    """Four rounds of olmo-1b serving 64 rows traced on a TPU v5e, the
    scheduler and transport holding a trace-level hub
    (``testdata_program/olmo_1b_4_rounds.xplane.pb``, kept out of
    ``testdata/``, which ``trace_reduce.load`` scans), with the
    op-to-scope map of that step
    (``testdata_program/olmo_1b_4_rounds.scopes.json``)."""
    data = os.path.join(HERE, "testdata_program", "olmo_1b_4_rounds")
    pd = ProfileData.from_file(data + ".xplane.pb")
    with open(data + ".scopes.json") as f:
        scopes = json.load(f)
    out = program_trace.program_layers(
        pd, scopes, scope=CACHE_IO, gap_span="sched.decode",
        stretch_span="bench.round", module_match="serve_step")
    assert out["steps"] == 4
    # the eight whole-cache passes: the scan's slices and updates of K
    # and V, and the layout copies into and out of the scan
    ops = dict(out["scoped_ops"])
    assert set(ops) == {"fusion.129", "fusion.130",
                        "dynamic-slice_bitcast_fusion.4",
                        "dynamic-slice_bitcast_fusion.5",
                        "copy.28", "copy.29", "copy.33", "copy.34"}
    assert ops["fusion.129"] == pytest.approx(0.009427199, rel=1e-4)
    assert out["scoped_s"] == pytest.approx(0.057801076, rel=1e-4)
    assert out["gap_idle_s"] == pytest.approx(0.00268896025, rel=1e-4)
    s = trace_reduce.summarize(pd, stretch_span="bench.round",
                               module_match="serve_step")
    assert s["module_count"] == 4
    assert out["scoped_s"] < s["module_s"]
    assert 4 * out["gap_idle_s"] <= s["window_s"] - s["busy_s"]
    # the program's spans are on the device trace's clock: each step
    # lies (but for the clocks' alignment, under a millisecond) inside
    # its own sched.decode span
    decode = trace_reduce.host_spans(pd, "sched.decode")
    (plane,) = trace_reduce.device_planes(pd)
    steps = [(a, b) for name, a, b in trace_reduce._line_events(
        plane, "XLA Modules") if "serve_step" in name]
    assert len(decode) == len(steps) == 4
    owner, tails = [], []
    for a, b in steps:
        cover = [min(b, y) - max(a, x) for _, x, y in decode]
        owner.append(cover.index(max(cover)))
        assert max(cover) > (b - a) - 1e-3
        tails.append(decode[owner[-1]][2] - b)
    assert sorted(owner) == [0, 1, 2, 3]
    # the idle inside sched.decode is mostly the wait from the step's end
    # on the device to the host's return with its tokens, not dispatch
    assert all(2e-3 < t < 3e-3 for t in tails), tails
    assert sum(tails) > 0.9 * 4 * out["gap_idle_s"]


def test_hub_window_reads_the_schedulers_own_time_and_the_wire():
    tele = Telemetry("timers")
    before = tele.snapshot()
    for _ in range(4):
        tele.registry.observe("span:sched.step", 5_000_000)
        tele.registry.observe("span:sched.decode", 3_000_000)
    for ns in (100_000, 300_000):
        tele.observe("serve.result_wire", ns)
    out = program_trace.hub_window(before, tele.snapshot())
    assert out["sched_self_s_per_round"] == pytest.approx(2e-3)
    assert out["result_wire_s"] == pytest.approx(2e-4)
    # what a hub at the default level (or a program without it) reads
    off = Telemetry("off").snapshot()
    assert program_trace.hub_window(off, off) == {}
    assert program_trace.hub_window({}, {}) == {}
