"""Each serving cell's step, compiled at the cell's sizes for a described
TPU v5e (no chip needed), must fit one chip's memory.  A later change that
grows the step's footprint past the chip fails here on the CPU."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

HBM_BYTES = 15.75 * 2**30        # what jax lets a program use on one v5e
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
SERVE_CELLS = [w["name"] for w in BENCH["workloads"]
               if json.load(open(os.path.join(
                   HERE, "traffic", w["traffic"] + ".json")))["driver"]
               == "serve_cohorts"]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_serve_step_fits_one_v5e(cell, one_chip):
    import importlib

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.serving.engine import init_cache, make_serve_step

    w = next(x for x in BENCH["workloads"] if x["name"] == cell)
    conf = json.load(open(os.path.join(HERE, "configs",
                                       w["config"] + ".json")))
    mix = json.load(open(os.path.join(HERE, "traffic",
                                      w["traffic"] + ".json")))
    ref = importlib.import_module("benchmarks.chip.reference."
                                  + conf["reference"])
    cfg = get_config(conf["program"])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda t: sds(*t), ref.param_shapes(conf["model"]),
        is_leaf=lambda t: isinstance(t, tuple) and isinstance(t[0], tuple))
    cache = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: init_cache(cfg, mix["cache_len"],
                                          mix["cohort_size"])))
    compiled = jax.jit(make_serve_step(cfg), donate_argnums=(1,)).lower(
        params, cache, sds((mix["cohort_size"],), jnp.int32)).compile()
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert 0 < peak <= HBM_BYTES, (cell, peak)
