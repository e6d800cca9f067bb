"""Helper: distributed loss AND gradients equal the local oracle, for all
families × {BSP, LCI_DEDICATED}.  Run with 8 fake devices ((2,4) mesh)."""
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.launch.mesh import make_mesh

from repro.core.modes import CommConfig, CommMode
from repro.distributed.comm import Comm, local_comm
from repro.models.common import ModelConfig
from repro.models.registry import build_model
from repro.optim import grad_sync

MESH = make_mesh((2, 4), ("data", "model"))
F = jnp.float32


def check(cfg, extra=None, extra_spec=None, grad_check=False):
    m = build_model(cfg)
    params, specs = m.init(jax.random.PRNGKey(0))
    s, b = 32, 4
    tokens = jax.random.randint(jax.random.PRNGKey(1), (s, b), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    bspec = {"tokens": P("model", "data"), "labels": P("model", "data")}
    if extra:
        batch.update(extra)
        bspec.update(extra_spec)

    loss_l, _ = jax.jit(lambda p, bt: m.loss(p, bt, local_comm()))(
        params, batch)
    grads_l = None
    if grad_check:
        grads_l = jax.jit(jax.grad(
            lambda p: m.loss(p, batch, local_comm())[0]))(params)

    pspecs = jax.tree_util.tree_map(lambda sp: sp.pspec(), specs)
    for mode in (CommMode.BSP, CommMode.LCI_DEDICATED):
        comm = Comm(CommConfig(mode=mode), model_axis="model",
                    data_axis="data")

        def dist_loss(p, bt):
            loss, _ = m.loss(p, bt, comm)
            return comm.pmean_data(loss)

        f = jax.jit(shard_map(dist_loss, mesh=MESH,
                                  in_specs=(pspecs, bspec), out_specs=P(),
                                  check_vma=False))
        loss_d = f(params, batch)
        d = abs(float(loss_l) - float(loss_d))
        assert d < 3e-3, (cfg.name, mode, float(loss_l), float(loss_d))
        print(f"OK loss {cfg.name:12s} {mode.value:14s} diff={d:.2e}")

        if grad_check:
            def dist_grads(p, bt):
                g = jax.grad(lambda pp: m.loss(pp, bt, comm)[0])(p)
                return grad_sync(g, specs, comm)

            fg = jax.jit(shard_map(dist_grads, mesh=MESH,
                                       in_specs=(pspecs, bspec),
                                       out_specs=pspecs, check_vma=False))
            grads_d = fg(params, batch)
            worst = 0.0
            for gl, gd in zip(jax.tree_util.tree_leaves(grads_l),
                              jax.tree_util.tree_leaves(grads_d)):
                gl, gd = np.asarray(gl), np.asarray(gd)
                denom = max(np.abs(gl).max(), 1e-3)
                worst = max(worst, float(np.abs(gl - gd).max() / denom))
            assert worst < 3e-2, (cfg.name, mode, worst)
            print(f"OK grad {cfg.name:12s} {mode.value:14s} "
                  f"rel_err={worst:.2e}")


def main():
    check(ModelConfig(name="planA", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=4, d_ff=128, vocab=256,
                      tp_target=4, dtype=F), grad_check=True)
    check(ModelConfig(name="planA-kvrep", family="dense", n_layers=2,
                      d_model=64, n_heads=8, n_kv_heads=2, d_ff=128,
                      vocab=256, tp_target=4, dtype=F, head_dim=16),
          grad_check=True)
    check(ModelConfig(name="planB-swa", family="dense", n_layers=2,
                      d_model=64, n_heads=3, n_kv_heads=3, d_ff=128,
                      vocab=256, tp_target=4, dtype=F, head_dim=16,
                      sliding_window=8, swa_every_nth_global=2))
    check(ModelConfig(name="moe", family="moe", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=4, d_ff=96, vocab=256,
                      n_experts=8, top_k=2, tp_target=4, dtype=F,
                      capacity_factor=8.0, shared_expert_ff=64),
          grad_check=True)
    check(ModelConfig(name="ssm", family="ssm", n_layers=2, d_model=64,
                      n_heads=0, n_kv_heads=0, d_ff=0, vocab=256,
                      ssm_state=16, ssm_headdim=16, ssm_chunk=8,
                      tp_target=4, dtype=F), grad_check=True)
    check(ModelConfig(name="hybrid", family="hybrid", n_layers=2,
                      d_model=64, n_heads=5, n_kv_heads=5, d_ff=128,
                      vocab=256, ssm_state=8, ssm_headdim=16, ssm_chunk=8,
                      tp_target=4, dtype=F, head_dim=16))
    check(ModelConfig(name="vlm", family="vlm", n_layers=4, d_model=64,
                      n_heads=4, n_kv_heads=4, d_ff=128, vocab=256,
                      cross_attn_every=2, tp_target=4, dtype=F),
          extra={"image_embeds": jax.random.normal(
              jax.random.PRNGKey(5), (8, 4, 64), F)},
          extra_spec={"image_embeds": P(None, "data", None)})
    check(ModelConfig(name="whisper", family="audio", n_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
                      vocab=256, norm="layernorm", mlp="gelu",
                      encoder_layers=2, tp_target=4, dtype=F,
                      tie_embeddings=True),
          extra={"frames": jax.random.normal(
              jax.random.PRNGKey(6), (16, 4, 64), F)},
          extra_spec={"frames": P("model", "data", None)})


if __name__ == "__main__":
    main()
    print("HELPER-OK")
