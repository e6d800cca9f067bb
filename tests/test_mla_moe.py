"""Latent attention and the routed-expert layer of the DeepSeek-V3
architecture (Moonlight-16B-A3B): the absorbed decode against the full
form, dropless decode routing, the expert-parallel share, the
selection-only bias, the leading dense layer and the latent cache's
size."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke
from repro.distributed.comm import local_comm
from repro.kernels.latent_decode.kernel import block_rows
from repro.models import lm
from repro.models.layers import greedy_sample, lm_head_logits
from repro.models.moe import moe_block, moe_decode, router_topk
from repro.models.registry import build_model
from repro.serving import engine
from repro.serving.engine import init_cache, make_serve_step
from repro.serving.kv_cache import token_cache_bytes

F = jnp.float32
ARCH = "moonshot-v1-16b-a3b"


def _f32(cfg, **kw):
    return dataclasses.replace(cfg, dtype=F, **kw)


def _params(cfg, seed=0):
    params, _ = build_model(cfg).init(jax.random.PRNGKey(seed))
    return params


def _layer(params, i=0):
    return jax.tree_util.tree_map(lambda a: a[i], params["layers"])


def test_absorbed_decode_matches_the_full_form():
    """Row by row through the latent cache, the absorbed attention gives
    what the expanded attention gives over the whole sequence."""
    cfg = _f32(get_smoke(ARCH))
    lp = _layer(_params(cfg))
    s, b = 12, 3
    x = jax.random.normal(jax.random.PRNGKey(3), (s, b, cfg.d_model), F)
    comm = local_comm()
    full = lm.mla_attention_op(x, lp, cfg, comm, q_offset=0)
    lat = init_cache(cfg, s, b).latent                   # (L, b, s, W)
    outs = []
    for p in range(s):
        out, row = engine._decode_mla(x[p], lp, cfg, comm, lat,
                                      jnp.int32(1), jnp.int32(p),
                                      joint_kv=False)
        rows = jnp.zeros((lat.shape[0],) + row.shape).at[1].set(row)
        lat = lat.at[1].set(engine._write_latent_rows(
            lat, rows, jnp.int32(p), comm, joint_kv=False)[1])
        outs.append(out)
    # f32 through two orders of the same products; outputs reach ~30
    np.testing.assert_allclose(np.stack(outs), np.asarray(full),
                               rtol=1e-4, atol=1e-4)
    # every row of layer 1 written, the padding left zero
    r = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    assert np.abs(np.asarray(lat[1, ..., :r])).min(axis=-1).min() > 0
    assert not np.asarray(lat[1, ..., r:]).any()


def test_prefill_then_decode_agree_with_one_forward():
    """Teacher-forced over a prompt through the cache, then greedy: every
    token is what one forward pass over the whole sequence ranks first."""
    cfg = _f32(get_smoke(ARCH))
    params = _params(cfg, seed=1)
    comm = local_comm()
    b, prompt, new = 2, 6, 8
    toks = jax.random.randint(jax.random.PRNGKey(4), (prompt, b), 0,
                              cfg.vocab)
    step = jax.jit(make_serve_step(cfg))
    cache = init_cache(cfg, prompt + new, b)
    seq = [toks[i] for i in range(prompt)]
    for i in range(prompt - 1):
        _, cache = step(params, cache, seq[i])
    for _ in range(new):
        nxt, cache = step(params, cache, seq[-1])
        seq.append(nxt)
    seq = jnp.stack(seq)                                  # (prompt+new, b)
    x, _ = build_model(cfg).forward(params, {"tokens": seq, "labels": seq},
                                    remat=False)
    head = params["lm_head"]
    best = jax.vmap(lambda xp: greedy_sample(
        lm_head_logits(xp, head, comm, real_vocab=cfg.vocab), comm))(x)
    np.testing.assert_array_equal(np.asarray(best[prompt - 1:-1]),
                                  np.asarray(seq[prompt:]))
    assert int(cache.length) == prompt + new - 1


def _per_token_moe(x, lp, cfg):
    """Each token through each expert it picked, one by one."""
    weights, experts, _, _ = router_topk(
        x.astype(F) @ lp["router"].astype(F), cfg, lp.get("router_bias"))
    out = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        for w, e in zip(np.asarray(weights[t]), np.asarray(experts[t])):
            e = int(e) - cfg.first_expert
            if not 0 <= e < cfg.n_experts_held:
                continue
            g, u = np.split(np.asarray(x[t] @ lp["we_in"][e]), 2)
            h = g / (1 + np.exp(-g)) * u
            out[t] += w * (h @ np.asarray(lp["we_out"][e]))
    return out


def test_decode_routing_is_dropless_above_the_old_capacity():
    """Every token picks experts 0 and 1 (the bias selects them): far
    past the capacity training's layer gives an expert, decode computes
    every one, and the counter says so."""
    cfg = _f32(get_smoke(ARCH))
    lp = _layer(_params(cfg, seed=2))
    lp["router_bias"] = jnp.zeros(cfg.n_experts).at[:2].set(10.0)
    t = 64
    x = jax.random.normal(jax.random.PRNGKey(5), (t, cfg.d_model), F)
    out, load = moe_decode(x, lp, cfg, local_comm())
    # f32, outputs reach a few hundred
    np.testing.assert_allclose(np.asarray(out), _per_token_moe(x, lp, cfg),
                               rtol=1e-4, atol=1e-3)
    assert np.asarray(load).tolist() == [[t, t] + [0] * 6, [t, t] + [0] * 6]
    _, aux = moe_block(x[:, None], lp, cfg, local_comm())
    # training's cap: ceil(64 * 2 / 8) * 2.0 = 32 slots an expert, so
    # half of the 128 picks are dropped there
    assert float(aux["dropped_frac"]) == 0.5


@pytest.mark.parametrize("path", ["decode", "train"])
def test_expert_shares_sum_to_the_uncut_layer(path):
    """The parts the eight one-expert shares give, with the shared expert
    counted once, add up to what the layer holding all experts gives."""
    cfg = _f32(get_smoke(ARCH), capacity_factor=64.0)
    params = _params(cfg, seed=3)
    lp = _layer(params)
    lp["router_bias"] = jax.random.normal(jax.random.PRNGKey(6),
                                          (cfg.n_experts,)) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(7), (10, cfg.d_model), F)
    comm = local_comm()

    def layer(c, p):
        if path == "decode":
            return moe_decode(x, p, c, comm)[0]
        return moe_block(x[:, None], p, c, comm)[0][:, 0]

    shared = lm._mlp_op(x, lp, cfg, comm, prefix="shared_")
    whole = layer(cfg, lp) + shared
    parts = shared
    for e in range(cfg.n_experts):
        share = dataclasses.replace(cfg, experts_held=1, first_expert=e)
        sp = dict(lp, we_in=lp["we_in"][e:e + 1],
                  we_out=lp["we_out"][e:e + 1])
        assert build_model(share).abstract_params()[0]["layers"][
            "we_in"].shape[1] == 1
        parts = parts + layer(share, sp)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=1e-4, atol=1e-3)


def test_bias_selects_and_never_weighs():
    cfg = get_smoke(ARCH)
    logits = jax.random.normal(jax.random.PRNGKey(8), (32, cfg.n_experts))
    bias = jnp.zeros(cfg.n_experts).at[-1].set(5.0)
    w0, e0, _, _ = router_topk(logits, cfg)
    w1, e1, _, _ = router_topk(logits, cfg, bias)
    assert (np.asarray(e1) == cfg.n_experts - 1).any(axis=1).all()
    assert not (np.asarray(e0) == np.asarray(e1)).all()
    s = jax.nn.sigmoid(logits)
    picked = jnp.take_along_axis(s, e1, axis=1)
    want = cfg.routed_scale * picked / picked.sum(axis=1, keepdims=True)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(want), rtol=1e-6)
    assert float(jnp.abs(w1.sum(axis=1) - cfg.routed_scale).max()) < 1e-5


def test_dense_first_layer_is_present():
    cfg = get_config(ARCH)
    shapes = build_model(cfg).abstract_params()[0]
    dense, moe = shapes["dense_layers"], shapes["layers"]
    assert dense["w_gate"].shape == (1, 2048, 11264)
    assert "router" not in dense and "we_in" not in dense
    assert moe["we_in"].shape == (26, 64, 2048, 2 * 1408)
    assert moe["router"].shape == (26, 2048, 64)
    assert moe["shared_w_gate"].shape == (26, 2048, 2816)
    assert moe["wkv_a"].shape == (26, 2048, 512 + 64)
    ep8 = build_model(get_config("moonlight-16b-a3b-ep8")).abstract_params()
    assert ep8[0]["layers"]["we_in"].shape == (26, 8, 2048, 2 * 1408)
    assert ep8[0]["layers"]["router"].shape == (26, 2048, 64)
    # the smoke model's serve step writes the dense layer's latent row
    small = get_smoke(ARCH)
    cache = init_cache(small, 4, 2)
    _, cache = jax.jit(make_serve_step(small))(_params(small), cache,
                                               jnp.array([1, 2], jnp.int32))
    assert np.abs(np.asarray(cache.latent[0, :, 0], np.float32)).sum() > 0
    assert not np.asarray(cache.latent[:, :, 1:]).any()
    assert cache.expert_load.shape == (3, 2, small.n_experts)
    assert int(cache.expert_load[0].sum()) == 2 * 2 * small.top_k


def test_latent_cache_is_sized_in_latent_bytes():
    cfg = get_config("moonlight-16b-a3b-ep8")
    # 512 latent + 64 rotary values, padded to 640 lanes, a layer
    assert token_cache_bytes(cfg) == 27 * 640 * 2
    c = jax.eval_shape(lambda: init_cache(cfg, 512, 256))
    assert c.k is None and c.v is None
    assert c.latent.shape == (27, 256, 512, 640)
    assert c.latent.size * 2 == 512 * 256 * token_cache_bytes(cfg)
    assert token_cache_bytes(get_config("olmo-1b")) == 16 * 2 * 2048 * 2


def test_cost_walker_counts_mla_dense_and_shared():
    """``launch/costs.py`` walks the serve step's jaxpr: its FLOPs are the
    latent attention (absorbed, over the cache's S rows and the new one),
    the dense first layer, the router, every held expert over every row,
    the combine, the shared expert and the head, 2 per multiply-add."""
    from repro.launch.costs import count_costs
    cfg = get_smoke(ARCH)
    b, s = 2, 8
    d, nq, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    w = init_cache(cfg, s, b).latent.shape[-1]
    mla = (d * nq * (dn + dr) + d * (r + dr) + nq * dn * r
           + nq * w * (s + 1) + nq * s * r + nq * r * dv + nq * dv * d)
    dense = 3 * d * cfg.dense_ff
    e = cfg.n_experts_held
    moe = (d * cfg.n_experts + e * 3 * d * cfg.d_ff + e * d
           + 3 * d * cfg.shared_expert_ff)
    per_row = cfg.n_layers * mla + dense + cfg.n_stacked_layers * moe \
        + cfg.padded_vocab * d
    params = build_model(cfg).abstract_params()[0]
    cache = jax.eval_shape(lambda: init_cache(cfg, s, b))
    jaxpr = jax.make_jaxpr(make_serve_step(cfg))(
        params, cache, jax.ShapeDtypeStruct((b,), jnp.int32))
    assert count_costs(jaxpr, {}).flops == 2 * b * per_row


def test_latent_blocks_counts_the_live_blocks_read():
    """From position 0, each step's attention reads, in every layer, the
    blocks that hold a row below the position: none at first, a second
    block once the position passes the first."""
    cfg = get_smoke(ARCH)
    seq, b, n = 256, 2, 140
    block = block_rows(seq)
    assert block < n - 1 < seq
    step = jax.jit(make_serve_step(cfg))
    params = _params(cfg)
    cache = init_cache(cfg, seq, b)
    toks = jnp.array([1, 2], jnp.int32)
    for _ in range(n):
        toks, cache = step(params, cache, toks)
    L = cache.latent.shape[0]
    assert int(cache.latent_blocks) == L * sum(-(-p // block)
                                               for p in range(n))


def test_latent_blocks_only_with_a_latent_cache():
    assert init_cache(get_smoke(ARCH), 8, 2).latent_blocks is not None
    assert init_cache(get_smoke("olmo-1b"), 8, 2).latent_blocks is None
