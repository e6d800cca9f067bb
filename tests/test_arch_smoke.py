"""Per-architecture smoke tests (assignment requirement): instantiate the
REDUCED config of each family and run one forward + one train step on CPU,
asserting output shapes and no NaNs.  The FULL configs are exercised only
via the dry-run (ShapeDtypeStruct, no allocation)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_config, get_smoke, SHAPES, cells
from repro.models.registry import build_model
from repro.optim import AdamWConfig
from repro.train import make_train_step, train_state_init

S, B = 32, 2


def _batch(cfg, key=0):
    rng = np.random.default_rng(key)
    tokens = rng.integers(0, cfg.vocab, size=(S, B)).astype(np.int32)
    batch = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(tokens)}
    if cfg.family == "vlm":
        batch["image_embeds"] = jnp.asarray(
            rng.standard_normal((max(cfg.n_image_tokens, 4), B,
                                 cfg.d_model)), cfg.dtype)
    if cfg.is_encdec:
        t = max(cfg.n_audio_frames, 16)
        batch["frames"] = jnp.asarray(
            rng.standard_normal((t, B, cfg.d_model)), cfg.dtype)
    return batch


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_smoke_forward(arch):
    cfg = get_smoke(arch)
    model = build_model(cfg)
    params, specs = model.init(jax.random.PRNGKey(0))
    x, aux = jax.jit(lambda p, b: model.forward(p, b))(params, _batch(cfg))
    assert x.shape == (S, B, cfg.d_model)
    assert np.isfinite(np.asarray(x, np.float32)).all()
    # specs pytree mirrors params exactly
    assert (jax.tree_util.tree_structure(params).num_leaves
            == len(jax.tree_util.tree_leaves(
                specs, is_leaf=lambda s: hasattr(s, "tp_axis"))))


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_smoke_train_step(arch):
    cfg = get_smoke(arch)
    model = build_model(cfg)
    opt = AdamWConfig(lr=1e-3)
    state, specs = train_state_init(model, jax.random.PRNGKey(0), opt)
    step = jax.jit(make_train_step(model, specs, opt))
    state, metrics = step(state, _batch(cfg))
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    assert float(metrics["grad_norm"]) > 0
    # a second step must also be finite (optimizer state exercised)
    state, metrics = step(state, _batch(cfg, key=1))
    assert np.isfinite(float(metrics["loss"]))


def test_full_configs_match_assignment():
    """The FULL configs carry the exact assigned dimensions."""
    expect = {
        "command-r-plus-104b": (64, 12288, 96, 8, 33792, 256000),
        "olmo-1b": (16, 2048, 16, 16, 8192, 50304),
        "gemma3-1b": (26, 1152, 4, 1, 6912, 262144),
        "minitron-8b": (32, 4096, 32, 8, 16384, 256000),
        "moonshot-v1-16b-a3b": (27, 2048, 16, 16, 1408, 163840),
        "olmoe-1b-7b": (16, 2048, 16, 16, 1024, 50304),
        "llama-3.2-vision-90b": (100, 8192, 64, 8, 28672, 128256),
        "mamba2-370m": (48, 1024, 0, 0, 0, 50280),
        "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001),
        "whisper-tiny": (4, 384, 6, 6, 1536, 51865),
    }
    for arch, (L, d, h, kv, ff, v) in expect.items():
        cfg = get_config(arch)
        assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                cfg.d_ff, cfg.vocab) == (L, d, h, kv, ff, v), arch


def test_moe_extras():
    c = get_config("moonshot-v1-16b-a3b")
    assert (c.n_experts, c.top_k) == (64, 6)
    c = get_config("olmoe-1b-7b")
    assert (c.n_experts, c.top_k) == (64, 8)


def test_ssm_extras():
    c = get_config("mamba2-370m")
    assert c.ssm_state == 128
    c = get_config("hymba-1.5b")
    assert c.ssm_state == 16


def test_cell_grid_is_40_with_documented_skips():
    grid = cells()
    assert len(grid) == 40
    skips = [(a, s) for a, s, ok, _ in grid if not ok]
    assert all(s == "long_500k" for _, s in skips)
    runs_long = {a for a, s, ok, _ in grid if s == "long_500k" and ok}
    assert runs_long == {"mamba2-370m", "hymba-1.5b", "gemma3-1b"}
    assert len(skips) == 7


def test_param_counts_sane():
    """Analytic parameter counts are in the advertised ballpark."""
    assert 90e9 < get_config("command-r-plus-104b").param_count() < 120e9
    assert 0.9e9 < get_config("olmo-1b").param_count() < 1.6e9
    assert 75e9 < get_config("llama-3.2-vision-90b").param_count() < 105e9
    # Moonlight-16B-A3B as published (MLA, a dense first layer, 26 MoE
    # layers of 64 experts top 6 with 2 shared): ~16B total, ~3B active
    moe = get_config("moonshot-v1-16b-a3b")
    assert 15e9 < moe.param_count() < 17e9
    assert 2.5e9 < moe.active_param_count() < 3.5e9
    assert 0.3e9 < get_config("mamba2-370m").param_count() < 0.6e9
