"""Helper: 2D-TP serving (weight-stationary decode) matches the classic
FSDP-gather decode AND the local oracle on a (2,4) mesh."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map
from repro.launch.mesh import make_mesh

from repro.core.modes import CommConfig, CommMode
from repro.distributed.comm import Comm, local_comm
from repro.models.common import ModelConfig
from repro.models.registry import build_model
from repro.serving.engine import cache_pspecs, init_cache, make_serve_step

MESH = make_mesh((2, 4), ("data", "model"))
F = jnp.float32


def check(cfg, batch=4):
    m = build_model(cfg)
    params, specs = m.init(jax.random.PRNGKey(0))
    S = 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (S, batch), 0,
                                cfg.vocab)
    comm = Comm(CommConfig(mode=CommMode.LCI_DEDICATED),
                model_axis="model", data_axis="data")
    pspecs = jax.tree_util.tree_map(lambda sp: sp.pspec(), specs)

    def run(tp2d):
        cspecs = cache_pspecs(cfg, batch=batch, tp2d=tp2d)
        tok_spec = P("data") if (batch > 1 and not tp2d) else P()
        serve = make_serve_step(cfg, comm, joint_kv=batch == 1, tp2d=tp2d)
        fn = jax.jit(shard_map(
            serve, mesh=MESH, in_specs=(pspecs, cspecs, tok_spec),
            out_specs=(tok_spec, cspecs), check_vma=False))
        cache = init_cache(cfg, S, batch)
        preds = []
        for i in range(S):
            nxt, cache = fn(params, cache, tokens[i])
            preds.append(np.asarray(nxt))
        return np.stack(preds)

    # local oracle
    serve_l = jax.jit(make_serve_step(cfg))
    cache = init_cache(cfg, S, batch)
    oracle = []
    for i in range(S):
        nxt, cache = serve_l(params, cache, tokens[i])
        oracle.append(np.asarray(nxt))
    oracle = np.stack(oracle)

    classic = run(False)
    tp2d = run(True)
    a1 = (classic == oracle).mean()
    a2 = (tp2d == oracle).mean()
    print(f"{cfg.name:10s} classic={a1:.3f} tp2d={a2:.3f}")
    assert a1 > 0.95 and a2 > 0.95, (cfg.name, a1, a2)


check(ModelConfig(name="dense", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=4, d_ff=128, vocab=256,
                  tp_target=4, dtype=F))
check(ModelConfig(name="gqa-par", family="dense", n_layers=2, d_model=64,
                  n_heads=8, n_kv_heads=2, d_ff=128, vocab=256, head_dim=16,
                  norm="layernorm", parallel_block=True, tie_embeddings=True,
                  tp_target=4, dtype=F))
check(ModelConfig(name="ssm", family="ssm", n_layers=2, d_model=64,
                  n_heads=0, n_kv_heads=0, d_ff=0, vocab=256, ssm_state=16,
                  ssm_headdim=16, ssm_chunk=8, tp_target=4, dtype=F))
check(ModelConfig(name="moe", family="moe", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=4, d_ff=96, vocab=256, n_experts=8,
                  top_k=2, tp_target=4, dtype=F, capacity_factor=8.0,
                  shared_expert_ff=64))
print("HELPER-OK")
