"""Helper: checkpoint under mesh A (2,4), restore + train under mesh B
(4,2) — the elastic re-shard path.  Run with 8 fake devices."""
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.checkpoint import CheckpointStore
from repro.core.modes import CommConfig, CommMode
from repro.data import SyntheticPipeline
from repro.distributed.comm import Comm
from repro.distributed.elastic import compatible_meshes, reshard_state
from repro.launch.mesh import make_mesh, shard
from repro.models.common import ModelConfig
from repro.models.registry import build_model
from repro.optim import AdamWConfig
from repro.optim.adamw import OptState
from repro.train import make_train_step, train_state_init
from repro.train.step import TrainState

CFG = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=4, d_ff=128, vocab=256, tp_target=4,
                  dtype=jnp.float32)
MKEYS = ("loss", "ce", "ntok", "aux_lb", "aux_z", "dropped_frac",
         "grad_norm")


def make_step(mesh, specs, model, opt):
    comm = Comm(CommConfig(mode=CommMode.LCI_DEDICATED),
                model_axis="model", data_axis="data")
    pspecs = jax.tree_util.tree_map(lambda sp: sp.pspec(), specs)
    sspecs = TrainState(pspecs, OptState(P(), pspecs, pspecs, pspecs))
    bspec = {"tokens": P("model", "data"), "labels": P("model", "data")}
    fn = shard_map(make_train_step(model, specs, opt, comm), mesh=mesh,
                       in_specs=(sspecs, bspec),
                       out_specs=(sspecs, {k: P() for k in MKEYS}),
                       check_vma=False)
    return jax.jit(fn), sspecs


def main():
    assert (2, 4) in compatible_meshes(CFG, 8)
    assert (4, 2) in compatible_meshes(CFG, 8)
    model = build_model(CFG)
    opt = AdamWConfig(lr=1e-3)
    state, specs = train_state_init(model, jax.random.PRNGKey(0), opt)
    pipe = SyntheticPipeline(vocab=256, seq_len=32, global_batch=8)
    wrap = lambda b: {k: jnp.asarray(v) for k, v in b.items()}

    mesh_a = make_mesh((2, 4), ("data", "model"))
    step_a, sspecs = make_step(mesh_a, specs, model, opt)
    for i in range(3):
        state, m = step_a(state, wrap(pipe.get_batch(i)))
    loss_a = float(m["loss"])

    with tempfile.TemporaryDirectory() as d:
        store = CheckpointStore(d)
        store.save(2, state, meta={"next_step": 3}, blocking=True)

        # ---- new mesh (4, 2): elastic restore ----
        mesh_b = make_mesh((4, 2), ("data", "model"))
        host_state, manifest = store.restore(
            jax.tree_util.tree_map(np.asarray, state))
        step_b, sspecs_b = make_step(mesh_b, specs, model, opt)
        state_b = reshard_state(host_state, shard(mesh_b, sspecs_b))
        # continue training on the new mesh — must be finite and sane
        for i in range(manifest["meta"]["next_step"], 6):
            state_b, m = step_b(state_b, wrap(pipe.get_batch(i)))
        assert np.isfinite(float(m["loss"])), m
        print(f"elastic OK: loss_a={loss_a:.4f} loss_b={float(m['loss']):.4f}")

        # cross-check against an unresharded continuation on mesh A
        state_a2, _ = store.restore(jax.tree_util.tree_map(np.asarray, state))
        for i in range(3, 6):
            state_a2, m2 = step_a(state_a2, wrap(pipe.get_batch(i)))
        d_loss = abs(float(m2["loss"]) - float(m["loss"]))
        assert d_loss < 2e-3, f"elastic diverged: {d_loss}"
        print(f"elastic continuation matches: d_loss={d_loss:.2e}")


if __name__ == "__main__":
    main()
    print("HELPER-OK")
