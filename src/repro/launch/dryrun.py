"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This is how the distribution config is proven coherent without hardware:
``jax.jit(step).lower(**ShapeDtypeStructs).compile()`` must succeed on the
(16,16) single-pod mesh AND the (2,16,16) multi-pod mesh for all 40 cells;
``memory_analysis()`` proves residency, ``cost_analysis()`` + HLO
collective parsing feed the roofline (EXPERIMENTS.md §Roofline).

Usage::

    python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
    python -m repro.launch.dryrun --all            # orchestrates subprocesses
    python -m repro.launch.dryrun --all --mesh multi

Artifacts: benchmarks/artifacts/dryrun/<arch>__<shape>__<mesh>__<mode>.json
"""
import argparse
import json
import math
import os
import re
import subprocess
import sys
import time
from typing import Any, Dict, Optional, Tuple

from jax import shard_map

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (ARCH_NAMES, SHAPES, cells, get_config,
                           shape_applicable)
from repro.core.modes import CommConfig, CommMode, parse_mode
from repro.launch.mesh import (batch_pspecs, data_axes, make_comm,
                               make_production_mesh, shard)
from repro.models.common import ModelConfig
from repro.models.registry import build_model
from repro.optim import AdamWConfig, adamw_init
from repro.serving.engine import cache_pspecs, init_cache
from repro.train.step import make_train_step

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "benchmarks", "artifacts", "dryrun")

SDS = jax.ShapeDtypeStruct

# Published per-chip peaks, keyed by jax's ``device_kind``.  Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
# 1,600 Gbit/s chip-to-chip interconnect per chip, over the four links
# of the 2-D torus.
PEAKS = {
    "TPU v5 lite": {"bf16_flop_s": 197e12, "hbm_byte_s": 819e9,
                    "ici_bit_s": 1_600e9, "ici_links": 4},
}
# the dry run compiles for CPU stand-ins of a v5e pod slice
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of one chip; a device without published peaks is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; add them to PEAKS with a source")
    return PEAKS[device_kind]


# ---------------------------------------------------------------------------
# abstract inputs per cell
# ---------------------------------------------------------------------------

def _pad_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def input_specs(cfg: ModelConfig, shape, mesh) -> Tuple[Dict, Dict]:
    """ShapeDtypeStruct stand-ins + pspecs for the batch of one cell."""
    s, b = shape.seq_len, shape.global_batch
    kind = shape.kind
    specs = batch_pspecs(cfg, kind, mesh, batch=b)
    batch: Dict[str, Any] = {}
    if kind == "decode":
        batch["tokens"] = SDS((b,), jnp.int32)
    else:
        batch["tokens"] = SDS((s, b), jnp.int32)
        if kind == "train":
            batch["labels"] = SDS((s, b), jnp.int32)
        else:
            specs.pop("labels", None)
    if cfg.family == "vlm" and kind != "decode":
        batch["image_embeds"] = SDS((cfg.n_image_tokens, b), jnp.bfloat16)
        batch["image_embeds"] = SDS(
            (cfg.n_image_tokens, b, cfg.d_model), cfg.dtype)
    if cfg.is_encdec and kind != "decode":
        t = _pad_to(cfg.n_audio_frames, 16)      # frames shard over model
        batch["frames"] = SDS((t, b, cfg.d_model), cfg.dtype)
    specs = {k: v for k, v in specs.items() if k in batch}
    return batch, specs


def n_memory_tokens(cfg: ModelConfig) -> int:
    if cfg.family == "vlm":
        return cfg.n_image_tokens
    if cfg.is_encdec:
        return _pad_to(cfg.n_audio_frames, 16)
    return 0


# ---------------------------------------------------------------------------
# HLO collective accounting
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s+((?:\w+\[[^\]]*\](?:\{[^}]*\})?,?\s*)+|\([^)]*\))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo: str) -> Dict[str, Any]:
    """Per-collective transfer accounting from optimized HLO text.

    Per-device transferred-bytes model (ring algorithms):
      collective-permute: result bytes (one hop);
      all-gather: result·(P-1)/P; reduce-scatter: result·(P-1);
      all-reduce: 2·result·(P-1)/P; all-to-all: result·(P-1)/P.
    """
    ops = []
    for m in _COLL_RE.finditer(hlo):
        shape_str, kind, _ = m.group(1), m.group(2), m.group(3)
        result_bytes = _shape_bytes(shape_str)
        tail = hlo[m.end():m.end() + 2000]
        g = _GROUPS_RE.search(tail)
        if g:
            p = len(g.group(1).split(","))
        else:
            gi = _GROUPS_IOTA_RE.search(tail)
            p = int(gi.group(2)) if gi else 1
        if kind == "collective-permute":
            xfer = result_bytes
        elif kind == "all-gather":
            xfer = result_bytes * (p - 1) // max(p, 1)
        elif kind == "reduce-scatter":
            xfer = result_bytes * (p - 1)
        elif kind == "all-reduce":
            xfer = 2 * result_bytes * (p - 1) // max(p, 1)
        else:                                   # all-to-all
            xfer = result_bytes * (p - 1) // max(p, 1)
        ops.append({"kind": kind, "result_bytes": result_bytes,
                    "group_size": p, "xfer_bytes": xfer})

    summary: Dict[str, Any] = {"n_ops": len(ops), "by_kind": {}, "ops": ops}
    for o in ops:
        k = summary["by_kind"].setdefault(
            o["kind"], {"count": 0, "xfer_bytes": 0})
        k["count"] += 1
        k["xfer_bytes"] += o["xfer_bytes"]
    summary["total_xfer_bytes"] = sum(o["xfer_bytes"] for o in ops)
    return summary


# ---------------------------------------------------------------------------
# cell construction
# ---------------------------------------------------------------------------

def build_cell(arch: str, shape_name: str, mesh, mode: CommMode,
               *, remat: bool = True, wire_bf16: bool = False):
    """Returns (jitted_fn, abstract_args tuple)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    model = build_model(cfg)
    comm = make_comm(mesh, CommConfig(mode=mode, wire_bf16=wire_bf16))
    daxes = data_axes(mesh)

    params_abs = jax.eval_shape(
        lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    _, pspecs_tree = model.abstract_params()
    param_pspecs = jax.tree_util.tree_map(
        lambda sp: sp.pspec(data_axis=daxes), pspecs_tree)
    batch_abs, bspecs = input_specs(cfg, shape, mesh)

    if shape.kind == "train":
        opt_cfg = AdamWConfig()
        opt_abs = jax.eval_shape(lambda p: adamw_init(p, opt_cfg),
                                 params_abs)
        from repro.optim.adamw import OptState
        from repro.train.step import TrainState
        state_abs = TrainState(params_abs, opt_abs)
        state_specs = TrainState(
            param_pspecs,
            OptState(step=P(), mu=param_pspecs, nu=param_pspecs,
                     master=param_pspecs))
        step = make_train_step(model, pspecs_tree, opt_cfg, comm,
                               remat=remat)
        metric_keys = ("loss", "ce", "ntok", "aux_lb", "aux_z",
                       "dropped_frac", "grad_norm")
        mspecs = {k: P() for k in metric_keys}
        fn = shard_map(step, mesh=mesh,
                           in_specs=(state_specs, bspecs),
                           out_specs=(state_specs, mspecs),
                           check_vma=False)
        jitted = jax.jit(fn, in_shardings=(shard(mesh, state_specs),
                                           shard(mesh, bspecs)),
                         donate_argnums=(0,))
        return jitted, fn, (state_abs, batch_abs)

    if shape.kind == "prefill":
        from repro.serving.engine import make_prefill_step
        prefill = make_prefill_step(cfg, comm)
        out_specs = (P(daxes), P(daxes, None))
        fn = shard_map(prefill, mesh=mesh,
                           in_specs=(param_pspecs, bspecs),
                           out_specs=out_specs, check_vma=False)
        jitted = jax.jit(fn, in_shardings=(shard(mesh, param_pspecs),
                                           shard(mesh, bspecs)))
        return jitted, fn, (params_abs, batch_abs)

    # decode
    from repro.serving.engine import make_serve_step
    b = shape.global_batch
    joint = b == 1
    serve = make_serve_step(cfg, comm, joint_kv=joint)
    cache_abs = jax.eval_shape(
        lambda: init_cache(cfg, shape.seq_len, b,
                           n_memory=n_memory_tokens(cfg)))
    cspecs = cache_pspecs(cfg, batch=b, data_axis=daxes)
    tok_spec = P() if joint else P(daxes)
    fn = shard_map(serve, mesh=mesh,
                       in_specs=(param_pspecs, cspecs, tok_spec),
                       out_specs=(tok_spec, cspecs), check_vma=False)
    jitted = jax.jit(fn, in_shardings=(shard(mesh, param_pspecs),
                                       shard(mesh, cspecs),
                                       NamedSharding(mesh, tok_spec)),
                     donate_argnums=(1,))
    return jitted, fn, (params_abs, cache_abs, batch_abs["tokens"])


# ---------------------------------------------------------------------------
# run one cell
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, multi_pod: bool, mode: CommMode,
             *, remat: bool = True, save: bool = True,
             wire_bf16: bool = False) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    mesh_name = "multi" if multi_pod else "single"
    variant = "+wirebf16" if wire_bf16 else ""
    tag = f"{arch}__{shape_name}__{mesh_name}__{mode.value}{variant}"
    if not ok:
        art = {"cell": tag, "status": "skipped", "reason": why}
        if save:
            _save(tag, art)
        print(f"[dryrun] {tag}: SKIP ({why})")
        return art

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    jitted, raw_fn, args = build_cell(arch, shape_name, mesh, mode,
                                      remat=remat, wire_bf16=wire_bf16)
    lowered = jitted.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_stats(hlo)

    # trip-count-exact per-device costs from the jaxpr (see costs.py)
    from repro.launch.costs import count_costs
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    jaxpr = jax.make_jaxpr(raw_fn)(*args)
    analytic = count_costs(jaxpr, axis_sizes)

    art: Dict[str, Any] = {
        "cell": tag, "status": "ok",
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "mode": mode.value, "n_devices": mesh.devices.size,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "flops_per_device": cost.get("flops", -1.0) if cost else -1.0,
        "bytes_accessed_per_device": cost.get("bytes accessed", -1.0)
        if cost else -1.0,
        "collectives": {k: v for k, v in coll.items() if k != "ops"},
        "n_collective_ops": coll["n_ops"],
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "analytic": analytic.as_dict(),
    }
    # roofline terms from the target chip's published peaks
    pk = peaks(TARGET_KIND)
    art["device_kind"] = TARGET_KIND
    n_dev = mesh.devices.size
    if shape.kind == "train":
        model_flops = 6.0 * cfg.active_param_count() * shape.seq_len \
            * shape.global_batch / n_dev
    elif shape.kind == "prefill":
        model_flops = 2.0 * cfg.active_param_count() * shape.seq_len \
            * shape.global_batch / n_dev
    else:
        model_flops = 2.0 * cfg.active_param_count() \
            * shape.global_batch / n_dev
    t_c = analytic.flops / pk["bf16_flop_s"]
    t_m = analytic.dot_bytes / pk["hbm_byte_s"]
    t_l = analytic.link_bytes / (pk["ici_bit_s"] / 8 / pk["ici_links"])
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_l),
              key=lambda kv: kv[1])
    # Overlap-aware bounds — the paper's claim made measurable on TPU:
    #   BSP (bulk-synchronous, the paper's MPI baseline): phases serialize,
    #       step >= t_c + t_m + t_l;
    #   LCI (async chunk streams): XLA overlaps independent channels,
    #       step >= max(t_c, t_m, t_l).
    # HBM traffic of the matmuls largely overlaps the MXU (systolic
    # pipelining), so the step-time proxies fold t_m into the compute phase
    # as max(t_c, t_m).
    phase_cm = max(t_c, t_m)
    bsp_bound = phase_cm + t_l
    lci_bound = max(phase_cm, t_l)
    art["roofline"] = {
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_l,
        "dominant": dom[0], "bound_s": dom[1],
        "bsp_bound_s": bsp_bound, "lci_bound_s": lci_bound,
        "overlap_speedup": bsp_bound / max(lci_bound, 1e-12),
        "model_flops_per_device": model_flops,
        "useful_flop_ratio": model_flops / max(analytic.flops, 1.0),
        # fraction of the overlapped step that is pure-MXU time
        "roofline_fraction": (t_c / lci_bound if lci_bound > 0 else 0.0),
    }
    if mem is not None:
        for field in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
            try:
                art[field] = int(getattr(mem, field))
            except Exception:
                pass
    if save:
        _save(tag, art)
        _save_ops(tag, coll["ops"])
    print(f"[dryrun] {tag}: OK  lower={t_lower:.1f}s compile={t_compile:.1f}s"
          f" flops/dev={art['flops_per_device']:.3g}"
          f" coll_bytes/dev={coll['total_xfer_bytes']:.3g}")
    return art


def _save(tag: str, art: Dict) -> None:
    os.makedirs(ART_DIR, exist_ok=True)
    with open(os.path.join(ART_DIR, tag + ".json"), "w") as f:
        json.dump(art, f, indent=1)


def _save_ops(tag: str, ops) -> None:
    os.makedirs(ART_DIR, exist_ok=True)
    with open(os.path.join(ART_DIR, tag + ".ops.json"), "w") as f:
        json.dump(ops, f)


# ---------------------------------------------------------------------------
# orchestrator
# ---------------------------------------------------------------------------

def main():
    # before any backend starts: jax fixes the device count on first use
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    ap.add_argument("--mode", default="lci_dedicated")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--wire-bf16", action="store_true",
                    help="bf16 ring accumulators (fp32 local adds)")
    ap.add_argument("--all", action="store_true",
                    help="run every applicable cell in subprocesses")
    ap.add_argument("--force", action="store_true",
                    help="recompute cells with existing artifacts")
    args = ap.parse_args()

    if args.all:
        failures = []
        for arch, shape_name, ok, why in cells():
            tag = (f"{arch}__{shape_name}__{args.mesh}__{args.mode}")
            path = os.path.join(ART_DIR, tag + ".json")
            if os.path.exists(path) and not args.force:
                with open(path) as f:
                    st = json.load(f).get("status")
                if st in ("ok", "skipped"):
                    print(f"[dryrun] {tag}: cached ({st})")
                    continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape_name,
                   "--mesh", args.mesh, "--mode", args.mode]
            if args.no_remat:
                cmd.append("--no-remat")
            r = subprocess.run(cmd, cwd=os.getcwd())
            if r.returncode != 0:
                failures.append(tag)
                _save(tag, {"cell": tag, "status": "failed"})
        if failures:
            print(f"[dryrun] FAILURES: {failures}")
            sys.exit(1)
        print("[dryrun] all cells OK")
        return

    if not args.arch or not args.shape:
        ap.error("--arch and --shape required (or --all)")
    run_cell(args.arch, args.shape, args.mesh == "multi",
             parse_mode(args.mode), remat=not args.no_remat,
             wire_bf16=args.wire_bf16)


if __name__ == "__main__":
    main()
