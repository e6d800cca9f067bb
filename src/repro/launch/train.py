"""Training launcher.

CPU-runnable end to end (smoke configs / small device counts), and the
same code path the dry-run proves out for the production meshes.

    # local single-device run of a reduced config
    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --smoke \
        --steps 50

    # 8 simulated devices, (2,4) mesh, LCI-dedicated collectives
    REPRO_DEVICES=8 PYTHONPATH=src python -m repro.launch.train \
        --arch olmo-1b --smoke --steps 20 --mesh 2x4 --mode lci_dedicated

Checkpoint/restart: pass --ckpt-dir; rerunning resumes from the last
committed step with exact data replay.
"""
import argparse
import dataclasses
import os
import time
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_NAMES, get_config, get_smoke
from repro.core.modes import CommConfig, parse_mode
from repro.data import SyntheticPipeline, stub_frames, stub_image_embeds
from repro.distributed.comm import Comm
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh, shard
from repro.models.registry import build_model
from repro.optim import AdamWConfig, cosine_schedule
from repro.optim.adamw import OptState
from repro.train import make_train_step, train_state_init
from repro.train.loop import LoopConfig, train_loop
from repro.train.step import TrainState


@dataclasses.dataclass
class TrainResult:
    history: List[Dict[str, float]]       # one metrics row per step
    state_bytes: Dict[str, int]           # train-state shard bytes/device
    memory: Dict[str, Dict[str, Any]]     # memory_stats() after init
    wall_s: float


def _per_device(state) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for leaf in jax.tree_util.tree_leaves(state):
        for sh in leaf.addressable_shards:
            out[str(sh.device)] = out.get(str(sh.device), 0) + sh.data.nbytes
    return out


def train(arch: str, *, smoke: bool = False, dtype: Any = None,
          steps: int = 50, seq: int = 64,
          batch: int = 8, lr: float = 1e-3, warmup: int = 10,
          mesh: str = "", mode: str = "lci_dedicated",
          attrs: Sequence[str] = (), ckpt_dir: str = "",
          ckpt_every: int = 20, metrics_csv: str = "") -> TrainResult:
    """Train ``arch`` for ``steps`` steps on synthetic data.  With
    ``mesh`` ("DxM") the step runs under shard_map on a (data, model)
    mesh and the train state is created already sharded on it.
    ``dtype`` replaces the config's parameter/activation dtype (f32
    leaves comm modes differing by reduction order only)."""
    cfg = get_smoke(arch) if smoke else get_config(arch)
    if dtype is not None:
        cfg = dataclasses.replace(cfg, dtype=dtype)
    model = build_model(cfg)
    opt = AdamWConfig(lr=cosine_schedule(lr, warmup, steps))
    key = jax.random.PRNGKey(0)

    pipe = SyntheticPipeline(vocab=cfg.vocab, seq_len=seq,
                             global_batch=batch)

    def extras(step):
        out = {}
        if cfg.family == "vlm":
            out["image_embeds"] = stub_image_embeds(
                max(cfg.n_image_tokens, 4), batch, cfg.d_model, step
            ).astype(np.float32)
        if cfg.is_encdec:
            t = max(((cfg.n_audio_frames + 15) // 16) * 16, 16)
            out["frames"] = stub_frames(t, batch, cfg.d_model, step
                                        ).astype(np.float32)
        return {k: jnp.asarray(v, cfg.dtype) for k, v in out.items()}

    if mesh:
        d, m = (int(x) for x in mesh.split("x"))
        mesh_ = make_mesh((d, m), ("data", "model"))
        from repro.core.attrs import parse_attr_args
        from repro.core.modes import _FIELD_TO_ATTR
        attr_over = parse_attr_args(list(attrs))
        fields = {f: attr_over[a] for f, a in _FIELD_TO_ATTR.items()
                  if a in attr_over}
        # the in-graph trainer only consumes CommConfig-mapped attrs;
        # reject the rest rather than silently dropping a valid name
        unused = set(attr_over) - set(_FIELD_TO_ATTR.values())
        if unused:
            raise ValueError(
                f"--attr {sorted(unused)} are host-runtime attributes; "
                f"the trainer's comm config accepts "
                f"{sorted(_FIELD_TO_ATTR.values())}")
        comm = Comm(CommConfig(**{"mode": parse_mode(mode), **fields}),
                    model_axis="model", data_axis="data")
        _, specs = model.abstract_params(key)
        pspecs = jax.tree_util.tree_map(lambda sp: sp.pspec(), specs)
        sspecs = TrainState(pspecs, OptState(P(), pspecs, pspecs, pspecs))
        # created sharded: no device ever holds the whole state
        state = jax.jit(lambda k: train_state_init(model, k, opt)[0],
                        out_shardings=shard(mesh_, sspecs))(key)
        step_inner = make_train_step(model, specs, opt, comm)
        bspec = {"tokens": P("model", "data"), "labels": P("model", "data")}
        if cfg.family == "vlm":
            bspec["image_embeds"] = P(None, "data", None)
        if cfg.is_encdec:
            bspec["frames"] = P("model", "data", None)
        mkeys = ("loss", "ce", "ntok", "aux_lb", "aux_z", "dropped_frac",
                 "grad_norm")
        step_fn = jax.jit(shard_map(
            step_inner, mesh=mesh_, in_specs=(sspecs, bspec),
            out_specs=(sspecs, {k: P() for k in mkeys}), check_vma=False),
            donate_argnums=(0,))
    else:
        if attrs:
            raise ValueError("--attr tunes the mesh comm config; it needs "
                             "--mesh (single-device runs have no comm)")
        state, specs = train_state_init(model, key, opt)
        step_fn = jax.jit(make_train_step(model, specs, opt),
                          donate_argnums=(0,))
    state_bytes = _per_device(state)
    memory = {str(dev): dev.memory_stats() or {}
              for dev in (mesh_.devices.flat if mesh else jax.devices()[:1])}

    def transform(batch, step):
        b = {k: jnp.asarray(v) for k, v in batch.items()}
        b.update(extras(step))
        return b

    loop_cfg = LoopConfig(
        total_steps=steps,
        ckpt_dir=ckpt_dir or None,
        ckpt_every=ckpt_every,
        metrics_csv=metrics_csv or None)
    t0 = time.perf_counter()
    state, hist = train_loop(state, step_fn, pipe, loop_cfg,
                             batch_transform=transform)
    return TrainResult(history=hist, state_bytes=state_bytes, memory=memory,
                       wall_s=time.perf_counter() - t0)


def main():
    # before any backend starts: jax fixes the device count on first use
    if os.environ.get("REPRO_DEVICES"):
        os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                                   + os.environ["REPRO_DEVICES"])
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_NAMES)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mesh", default="",
                    help="e.g. 2x4 => (data=2, model=4); empty = local")
    ap.add_argument("--mode", default="lci_dedicated")
    ap.add_argument("--attr", action="append", default=[],
                    metavar="NAME=VALUE",
                    help="runtime-level attribute override for the comm "
                         "config (repeatable; e.g. --attr n_channels=8 "
                         "— DESIGN.md §12)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--metrics-csv", default="")
    args = ap.parse_args()

    enable_compile_cache()
    try:
        res = train(args.arch, smoke=args.smoke, steps=args.steps,
                    seq=args.seq, batch=args.batch, lr=args.lr,
                    mesh=args.mesh, mode=args.mode, attrs=args.attr,
                    ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                    metrics_csv=args.metrics_csv)
    except ValueError as e:
        raise SystemExit(str(e))
    hist = res.history
    print(f"[train] {len(hist)} steps in {res.wall_s:.1f}s; "
          f"loss {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
