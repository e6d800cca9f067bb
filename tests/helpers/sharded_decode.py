"""Helper: the sharded decode (FSDP weights gathered per layer) matches
the local oracle on a (2,4) mesh, in its tokens and in its K/V cache:
halfway through, the rows written match the oracle's and the rest are
zero, whether the cache is seq-sharded over model (batch over data) or
over data and model jointly (B == 1)."""
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


def _kv(cache):
    return [np.asarray(a) for a in (cache.k, cache.v) if a is not None]


def check_cache(cfg, got, want, half):
    """Each snapshot's K/V equals the oracle's; rows at and past ``half``
    are still zero halfway through."""
    for g, w in zip(got, want):
        for a, b in zip(g, w):
            # sharded sums round differently: to the oracle's scale
            np.testing.assert_allclose(a, b, rtol=0,
                                       atol=2e-5 * np.abs(b).max(),
                                       err_msg=cfg.name)
    for a in got[0]:
        assert not a[:, half:].any(), cfg.name


def check(cfg, batch=4):
    m = build_model(cfg)
    params, specs = m.init(jax.random.PRNGKey(0))
    S = 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (S, batch), 0,
                                cfg.vocab)
    comm = Comm(CommConfig(mode=CommMode.LCI_DEDICATED),
                model_axis="model", data_axis="data")
    pspecs = jax.tree_util.tree_map(lambda sp: sp.pspec(), specs)

    def run():
        cspecs = cache_pspecs(cfg, batch=batch)
        tok_spec = P("data") if batch > 1 else P()
        serve = make_serve_step(cfg, comm, joint_kv=batch == 1)
        fn = jax.jit(shard_map(
            serve, mesh=MESH, in_specs=(pspecs, cspecs, tok_spec),
            out_specs=(tok_spec, cspecs), check_vma=False))
        cache = init_cache(cfg, S, batch)
        preds, snaps = [], []
        for i in range(S):
            nxt, cache = fn(params, cache, tokens[i])
            preds.append(np.asarray(nxt))
            if i + 1 in (S // 2, S):
                snaps.append(_kv(cache))
        return np.stack(preds), snaps

    # local oracle
    serve_l = jax.jit(make_serve_step(cfg))
    cache = init_cache(cfg, S, batch)
    oracle, want = [], []
    for i in range(S):
        nxt, cache = serve_l(params, cache, tokens[i])
        oracle.append(np.asarray(nxt))
        if i + 1 in (S // 2, S):
            want.append(_kv(cache))
    oracle = np.stack(oracle)

    sharded, sharded_kv = run()
    agree = (sharded == oracle).mean()
    print(f"{cfg.name:10s} b={batch} sharded={agree:.3f}")
    assert agree > 0.95, (cfg.name, agree)
    check_cache(cfg, sharded_kv, want, S // 2)


check(ModelConfig(name="dense", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=4, d_ff=128, vocab=256,
                  tp_target=4, dtype=F))
# B == 1: the KV seq dim sharded over data and model jointly (joint_kv)
check(ModelConfig(name="dense", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=4, d_ff=128, vocab=256,
                  tp_target=4, dtype=F), batch=1)
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
