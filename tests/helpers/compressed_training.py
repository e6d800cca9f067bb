"""Helper: int8+error-feedback gradient compression converges like the
uncompressed baseline on a (2,4) mesh.  Run with 8 fake devices."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.launch.mesh import make_mesh

from repro.core.modes import CommConfig, CommMode
from repro.data import SyntheticPipeline
from repro.distributed.comm import Comm
from repro.distributed.compression import (grad_sync_compressed,
                                           init_error_state)
from repro.models.common import ModelConfig
from repro.models.registry import build_model
from repro.optim import AdamWConfig, adamw_init, adamw_update, grad_sync
from repro.optim.adamw import OptState

CFG = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=4, d_ff=128, vocab=64, tp_target=4,
                  dtype=jnp.float32)
MESH = make_mesh((2, 4), ("data", "model"))


def run(compressed: bool, steps: int = 30):
    model = build_model(CFG)
    opt = AdamWConfig(lr=3e-3, weight_decay=0.0, max_grad_norm=0.0)
    params, specs = model.init(jax.random.PRNGKey(0))
    opt_state = adamw_init(params, opt)
    error = init_error_state(params)
    comm = Comm(CommConfig(mode=CommMode.LCI_DEDICATED),
                model_axis="model", data_axis="data")
    pspecs = jax.tree_util.tree_map(lambda sp: sp.pspec(), specs)
    bspec = {"tokens": P("model", "data"), "labels": P("model", "data")}
    err_specs = pspecs

    def step(params, opt_state, error, batch):
        def loss_fn(p):
            loss, m = model.loss(p, batch, comm)
            return loss, m
        (loss, m), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        if compressed:
            grads, error = grad_sync_compressed(grads, specs, error, comm)
        else:
            grads = grad_sync(grads, specs, comm)
        params, opt_state = adamw_update(grads, opt_state, params, opt)
        return params, opt_state, error, comm.pmean_all(loss)

    sspec = OptState(P(), pspecs, pspecs, pspecs)
    f = jax.jit(shard_map(
        step, mesh=MESH,
        in_specs=(pspecs, sspec, err_specs, bspec),
        out_specs=(pspecs, sspec, err_specs, P()), check_vma=False))
    pipe = SyntheticPipeline(vocab=64, seq_len=32, global_batch=8)
    losses = []
    for i in range(steps):
        batch = {k: jnp.asarray(v) for k, v in pipe.get_batch(i).items()}
        params, opt_state, error, loss = f(params, opt_state, error, batch)
        losses.append(float(loss))
    return losses


def main():
    base = run(False)
    comp = run(True)
    print(f"baseline:   {base[0]:.3f} -> {np.mean(base[-5:]):.3f}")
    print(f"compressed: {comp[0]:.3f} -> {np.mean(comp[-5:]):.3f}")
    # compressed training must learn, and track the baseline closely
    assert np.mean(comp[-5:]) < comp[0] - 0.3
    assert abs(np.mean(comp[-5:]) - np.mean(base[-5:])) < 0.4, \
        (np.mean(comp[-5:]), np.mean(base[-5:]))


if __name__ == "__main__":
    main()
    print("HELPER-OK")
