"""Pallas kernel sweeps: shapes × dtypes, assert_allclose vs ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention_tpu
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.latent_decode.kernel import NEG_INF as LATENT_NEG_INF
from repro.kernels.latent_decode.kernel import latent_decode_tpu
from repro.kernels.latent_decode.ref import latent_decode_ref
from repro.kernels.rmsnorm.kernel import rmsnorm_tpu
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.ssd_scan.kernel import ssd_scan_tpu
from repro.kernels.ssd_scan.ref import ssd_scan_ref
from repro.kernels.moe_gmm.kernel import BLOCK_F, moe_gmm_tpu
from repro.kernels.moe_gmm.ref import moe_gmm_ref


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 5e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hq,hkv,sq,skv,dh,causal,window,q_offset", [
    (2, 4, 2, 64, 64, 16, True, 0, 0),
    (1, 4, 1, 128, 128, 32, True, 32, 0),
    (2, 2, 2, 64, 128, 16, True, 0, 64),      # SP: local q, longer kv
    (1, 6, 3, 96, 96, 16, False, 0, 0),       # encoder (bidirectional)
    (1, 8, 8, 32, 32, 64, True, 8, 0),        # MHA + window
])
def test_flash_attention_sweep(b, hq, hkv, sq, skv, dh, causal, window,
                               q_offset, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, hq, sq, dh), dtype)
    k = jax.random.normal(ks[1], (b, hkv, skv, dh), dtype)
    v = jax.random.normal(ks[2], (b, hkv, skv, dh), dtype)
    got = flash_attention_tpu(q, k, v, causal=causal, window=window,
                              q_offset=q_offset, block_q=32, block_k=32,
                              interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window,
                              q_offset=q_offset)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows,d,block", [(8, 64, 4), (64, 128, 16),
                                          (100, 96, 32), (1, 256, 8)])
def test_rmsnorm_sweep(rows, d, block, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (rows, d), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (d,), dtype)
    got = rmsnorm_tpu(x, w, block_rows=block, interpret=True)
    ref = rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bs,h,s,p,g,n,chunk", [
    (2, 4, 64, 16, 2, 8, 16),
    (1, 4, 128, 32, 1, 16, 32),
    (3, 6, 48, 8, 3, 4, 16),
])
def test_ssd_sweep(bs, h, s, p, g, n, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (bs, h, s, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bs, h, s))).astype(dtype)
    a_log = (jax.random.normal(ks[2], (h,)) * 0.5).astype(jnp.float32)
    b = (jax.random.normal(ks[3], (bs, g, s, n)) * 0.3).astype(dtype)
    c = (jax.random.normal(ks[4], (bs, g, s, n)) * 0.3).astype(dtype)
    d = jax.random.normal(ks[5], (h,)).astype(jnp.float32)
    got = ssd_scan_tpu(x, dt, a_log, b, c, d, chunk=chunk, interpret=True)
    ref = ssd_scan_ref(x, dt, a_log, b, c, d)
    tol = 5e-2 if dtype == jnp.bfloat16 else 5e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("act", ["swiglu", "geglu", "gelu", "relu2"])
@pytest.mark.parametrize("e,cap,d,f,block", [(4, 32, 48, 24, 8),
                                             (2, 64, 32, 64, 32),
                                             (2, 32, 32, 2 * BLOCK_F, 16)])
def test_moe_gmm_sweep(e, cap, d, f, block, act, dtype):
    mult = 2 if act in ("swiglu", "geglu") else 1
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (e, cap, d), dtype)
    w1 = (jax.random.normal(ks[1], (e, d, mult * f)) * 0.2).astype(dtype)
    w2 = (jax.random.normal(ks[2], (e, f, d)) * 0.2).astype(dtype)
    got = moe_gmm_tpu(x, w1, w2, act=act, block_c=block, interpret=True)
    ref = moe_gmm_ref(x, w1, w2, act=act)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_flash_matches_model_attention():
    """The kernel and the model stack's scan-flash agree (same oracle)."""
    from repro.models.attention import flash_attention as model_flash
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (64, 2, 4, 16))      # (s, b, h, dh)
    k = jax.random.normal(ks[1], (64, 2, 2, 16))
    v = jax.random.normal(ks[2], (64, 2, 2, 16))
    a = model_flash(q, k, v, causal=True, block_q=16, block_k=16)
    b = flash_attention_tpu(q.transpose(1, 2, 0, 3), k.transpose(1, 2, 0, 3),
                            v.transpose(1, 2, 0, 3), causal=True,
                            block_q=16, block_k=16,
                            interpret=True).transpose(2, 0, 1, 3)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def _latent_inputs(dtype, b=16, seq=512, width=256, nq=4, layers=2):
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    qc = jax.random.normal(ks[0], (b, nq, width), jnp.float32) * 0.1
    lat = jax.random.normal(ks[1], (layers, b, seq, width)).astype(dtype)
    return qc, lat


def _latent_tol(dtype):
    # the kernel's products take the query and the probabilities in the
    # cache's dtype, the oracle keeps them in f32
    return 3e-2 if dtype == jnp.bfloat16 else 1e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("block,batch_tile", [(128, 8), (64, 16)])
@pytest.mark.parametrize("live", [0, 1, 127, 128, 129, 300, 511, 512])
def test_latent_decode_sweep(live, block, batch_tile, dtype):
    qc, lat = _latent_inputs(dtype)
    got = latent_decode_tpu(qc, lat, 1, live, rank=128, block=block,
                            batch_tile=batch_tile, interpret=True)
    want = latent_decode_ref(qc, lat, 1, live, rank=128)
    tol = _latent_tol(dtype)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=tol,
                                   rtol=tol)
    if live == 0:
        assert (np.asarray(got[1]) == LATENT_NEG_INF).all()
        assert not np.asarray(got[0]).any() and not np.asarray(got[2]).any()


@pytest.mark.parametrize("pos", [200, 300])
def test_latent_decode_seq_shards_combine(pos):
    """Four 128-row shards, each given its own live count: the wholly dead
    shards read nothing and drop out of the flash combine, which gives
    what one read of the whole cache gives."""
    qc, lat = _latent_inputs(jnp.float32)
    parts = [latent_decode_tpu(qc, lat[:, :, i * 128:(i + 1) * 128], 1,
                               min(max(pos - i * 128, 0), 128), rank=128,
                               block=64, batch_tile=8, interpret=True)
             for i in range(4)]
    dead = [i for i in range(4) if i * 128 >= pos]
    assert dead and pos % 128          # one shard partly live, some dead
    for i in dead:
        assert not np.asarray(parts[i][2]).any()
    m_g = np.max([np.asarray(m) for _, m, _ in parts], axis=0)
    num = sum(np.asarray(n) * np.exp(np.asarray(m) - m_g)[..., None]
              for n, m, _ in parts)
    den = sum(np.asarray(l) * np.exp(np.asarray(m) - m_g)
              for _, m, l in parts)
    wn, wm, wl = latent_decode_ref(qc, lat, 1, pos, rank=128)
    np.testing.assert_allclose(num / den[..., None],
                               np.asarray(wn / wl[..., None]), atol=1e-5,
                               rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("live", [1, 129, 300])
def test_latent_decode_ignores_dead_rows(live, dtype):
    """Rows at and past ``live`` hold NaN: the output is finite and the
    same as with those rows zero."""
    qc, lat = _latent_inputs(dtype)
    dead = (jnp.arange(lat.shape[2]) >= live)[None, None, :, None]
    zero = latent_decode_tpu(qc, jnp.where(dead, 0, lat), 1, live, rank=128,
                             block=128, batch_tile=8, interpret=True)
    nan = latent_decode_tpu(qc, jnp.where(dead, jnp.nan, lat), 1, live,
                            rank=128, block=128, batch_tile=8,
                            interpret=True)
    for z, n in zip(zero, nan):
        assert np.isfinite(np.asarray(n)).all()
        np.testing.assert_array_equal(np.asarray(n), np.asarray(z))
