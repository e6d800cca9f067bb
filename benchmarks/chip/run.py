#!/usr/bin/env python3
"""Run one cell of the chip benchmark once and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix, entry path or
per-layer metric sits in a file of its own, found by the names in
``BENCHMARK.json``: ``configs/<config>.json`` (sizes, source, the name of
its plain reference under ``reference/`` and the limit of its check),
``traffic/<traffic>.json`` (the mix, and the driver under ``drivers/``
that serves it) and ``metrics/<metric>.py`` (a ``read(run)`` that returns
the metric, or None where it finds nothing to read).

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window's first rounds.
Both decide ``correct`` the same way (``compare.py``).  The last line of
standard output is one JSON object; the numbers compared, each beside its
limit, are the last lines of standard error.  A run that finds no TPU, or
fewer chips than the cell asks for, exits 1 and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_metrics(bench: dict, cell: str, kind: str) -> list:
    """The metrics of ``kind`` (end_to_end or per_layer) the cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in names)]


def load_file(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metric(name: str, run) -> object:
    mod = load_file(os.path.join(HERE, "metrics", name + ".py"),
                    "bench_metric_" + name.replace(".", "_"))
    return mod.read(run)


class Context(types.SimpleNamespace):
    """What a driver is handed: the program's config, the mix, the weights,
    the spans, and the window's hooks (which start and stop the trace)."""

    def window_start(self) -> float:
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            import jax
            jax.profiler.start_trace(self.trace_dir)
            self.tracing, self.trace_left = True, self.mix["trace_rounds"]
        self.t_window = time.perf_counter()
        return self.t_window

    def after_round(self) -> None:
        if self.tracing:
            self.trace_left -= 1
            if self.trace_left <= 0:
                self._stop_trace()

    def window_end(self) -> None:
        if self.tracing:
            self._stop_trace()

    def _stop_trace(self) -> None:
        import jax
        jax.profiler.stop_trace()
        self.tracing = False


def check_program(cfg, fields: dict) -> None:
    bad = {k: (v, getattr(cfg, k)) for k, v in fields.items()
           if getattr(cfg, k) != v}
    if bad:
        raise ValueError(f"the program's config departs from the "
                         f"configuration file (file, program): {bad}")


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             bench: dict = None, chip: bool = True,
             overrides: dict = None, controls=()) -> dict:
    """One run of one cell; returns the result line as a dict.  ``chip``
    False skips the look for a TPU and the compile cache (tests only);
    ``overrides`` replaces the file's model dims, program config or parts
    of the mix (tests only); ``controls`` also reads each named control
    on the same sample (``calibrate.py`` only)."""
    bench = bench or load_json(os.path.join(ROOT, "BENCHMARK.json"))
    over = overrides or {}
    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    conf = load_json(os.path.join(HERE, "configs", cell["config"] + ".json"))
    mix = {**load_json(os.path.join(HERE, "traffic",
                                    cell["traffic"] + ".json")),
           **over.get("mix", {})}
    dims = over.get("model", conf["model"])

    import jax
    devices = jax.devices()
    if chip:
        if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
            raise SystemExit(f"cell {cell_name} needs {cell['chips']} TPU "
                             f"chip(s); jax found {len(devices)} "
                             f"{devices[0].platform} device(s)")
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = devices[:cell["chips"]]

    from benchmarks.chip import compare
    from benchmarks.chip.reference import weights
    from benchmarks.chip.spans import Spans
    from repro.configs import get_config
    from repro.models.registry import build_model

    ref = importlib.import_module("benchmarks.chip.reference."
                                  + conf["reference"])
    cfg = over.get("program_cfg") or get_config(conf["program"])
    check_program(cfg, ref.program_fields(dims))
    weights.check_layout(ref.param_shapes(dims),
                         build_model(cfg).abstract_params()[0])
    params = ref.init_params(dims, seed)
    jax.block_until_ready(params)

    ctx = Context(program_cfg=cfg, mix=mix, params=params, seed=seed,
                  vocab=dims["vocab_size"], seconds=seconds, trace=trace,
                  tracing=False, spans=Spans(annotate=trace))
    driver = importlib.import_module("benchmarks.chip.drivers."
                                     + mix["driver"])
    res = driver.run(ctx)
    setup_s = ctx.t_window - T0
    summary = {}
    if trace:
        from benchmarks.chip import trace_reduce
        summary = trace_reduce.summarize(
            trace_reduce.load(ctx.trace_dir), stretch_span="bench.round",
            module_match="serve_step")
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    gc.collect()

    # -- correct: delivery, then the reference over a sample --------------
    check = mix["check"]
    rids = compare.sample(res["served"], seed, check["requests"],
                          check["longest"])
    gap, n_tok = compare.widest_gap(ref, dims, params, res["served"], rids,
                                    mix["cache_len"])
    control = {c: compare.widest_gap(ref, dims, params, res["served"], rids,
                                     mix["cache_len"], control=c)[0]
               for c in controls}
    checks = res["checks"] + [("logit_gap", gap, conf["check"]["logit_gap"])]
    correct = all(v <= lim for _, v, lim in checks)

    if trace:
        run = types.SimpleNamespace(layer=res["layer"], trace=summary,
                                    ref=ref, dims=dims,
                                    device_kind=devices[0].device_kind)
        metrics = {}
        for m in cell_metrics(bench, cell_name, "per_layer"):
            v = read_metric(m["name"], run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = {**res["e2e"], "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell_metrics(bench, cell_name, "end_to_end")}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    if trace and summary:
        device["busy_s"], device["window_s"] = (summary["busy_s"],
                                                summary["window_s"])
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    out["tokens_compared"] = n_tok
    if control:
        out["control_gap"] = control
    out["checks"] = {name: {"value": v, "limit": lim}
                     for name, v, lim in checks}
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    print(f"served tokens compared with the reference: "
          f"{out['tokens_compared']}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
