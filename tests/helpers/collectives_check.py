import os
# XLA_FLAGS set by conftest (8 devices)
import sys
# PYTHONPATH set by conftest
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_mesh
from repro.core import collectives as C
from repro.core.modes import CommConfig, CommMode

mesh = make_mesh((8,), ("x",))
key = jax.random.PRNGKey(0)
X = jax.random.normal(key, (16, 32), jnp.float32)
W = jax.random.normal(jax.random.PRNGKey(1), (32, 24), jnp.float32)
W2 = jax.random.normal(jax.random.PRNGKey(2), (4, 24), jnp.float32)  # k_shard=4 per rank

modes = [CommConfig(mode=m) for m in CommMode]

def smap(f, in_specs, out_specs):
    return jax.jit(shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False))

ok = True
for cfg in modes:
    # all_gather
    f = smap(lambda x: C.all_gather(x, "x", cfg), (P("x", None),), P(None, None))
    got = f(X)
    exp = np.tile(X, (1, 1))  # gathered = X itself replicated
    if not np.allclose(got, X):
        print(f"AG FAIL {cfg.mode}"); ok = False
    # all_gather_matmul
    f = smap(lambda x, w: C.all_gather_matmul(x, w, "x", cfg), (P("x", None), P(None, None)), P(None, None))
    got = f(X, W)
    exp = X @ W
    if not np.allclose(got, exp, atol=1e-4):
        print(f"AGMM FAIL {cfg.mode}", np.abs(got-exp).max()); ok = False
    # matmul_reduce_scatter: x (m, k) sharded on k over ranks; w (k, n) sharded on k
    Xk = jax.random.normal(key, (16, 32), jnp.float32)
    Wk = jax.random.normal(jax.random.PRNGKey(3), (32, 24), jnp.float32)
    f = smap(lambda x, w: C.matmul_reduce_scatter(x, w, "x", cfg), (P(None, "x"), P("x", None)), P("x", None))
    got = f(Xk, Wk)
    exp = Xk @ Wk
    if not np.allclose(got, exp, atol=1e-3):
        print(f"MMRS FAIL {cfg.mode}", np.abs(got-exp).max()); ok = False
    # reduce_scatter on raw tensor: input replicated per rank? semantics: each rank has local x, result = sum over ranks scattered
    f = smap(lambda x: C.reduce_scatter(x, "x", cfg), (P(None, None),), P("x", None))
    got = f(X)  # each rank's local copy is X -> sum = 8*X, scattered rows
    if not np.allclose(got, 8*X, atol=1e-3):
        print(f"RS FAIL {cfg.mode}", np.abs(got-8*X).max()); ok = False
    # all_reduce
    f = smap(lambda x: C.all_reduce(x, "x", cfg), (P(None, None),), P(None, None))
    got = f(X)
    if not np.allclose(got, 8*X, atol=1e-3):
        print(f"AR FAIL {cfg.mode}", np.abs(got-8*X).max()); ok = False
    # all_to_all
    Y = jax.random.normal(key, (8, 16, 8), jnp.float32)
    f = smap(lambda x: C.all_to_all(x, "x", split_axis=1, concat_axis=0, config=cfg), (P("x", None, None),), P("x", None, None))
    got = f(Y)
    exp_f = smap(lambda x: jax.lax.all_to_all(x, "x", split_axis=1, concat_axis=0, tiled=True), (P("x", None, None),), P("x", None, None))
    if not np.allclose(got, exp_f(Y)):
        print(f"A2A FAIL {cfg.mode}"); ok = False

# barrier / tree collectives
f = smap(lambda: C.dissemination_barrier("x")[None], (), P("x"))
tok = f()
assert np.all(np.asarray(tok) == 8), tok
val = jnp.arange(8.0).reshape(8,1) + 3
f = smap(lambda v: C.tree_broadcast(v.squeeze(0), "x", root=3)[None], (P("x", None),), P("x", None))
got = f(val)
assert np.allclose(got, 6.0), got   # rank 3's value = 3+3
f = smap(lambda v: C.tree_reduce(v.squeeze(0), "x", root=0)[None], (P("x", None),), P("x", None))
got = f(val)
assert np.allclose(np.asarray(got)[0], np.sum(np.asarray(val))), got
print("barrier/tree OK")
assert ok, "collective failures"
print("HELPER-OK")
