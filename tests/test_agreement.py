"""The decode-vs-forward check that ``chip_smoke.py`` applies at full
width must pass the engine's serve step and fail a broken one: planted
faults in the cache write and the rope position (olmo-1b smoke size)."""
import itertools

import jax.numpy as jnp
import pytest

from repro.launch.serve import build
from repro.serving import agreement as ag
from repro.serving import engine
from repro.serving.engine import DecodeCache, make_serve_step

LENGTH, BATCH = 32, 2


@pytest.fixture(scope="module")
def olmo():
    return build("olmo-1b", smoke=True)


def test_engine_decode_passes(olmo):
    res = ag.decode_agreement(*olmo, length=LENGTH, batch=BATCH)
    assert res.failures() == [], res


def _drop_odd_cache_writes(cfg):
    step = make_serve_step(cfg)

    def broken(params, cache, tokens):
        nxt, new = step(params, cache, tokens)
        keep = cache.length % 2 == 1          # odd positions never land
        return nxt, DecodeCache(k=jnp.where(keep, cache.k, new.k),
                                v=jnp.where(keep, cache.v, new.v),
                                length=new.length)
    return broken


def test_dropped_cache_write_fails(olmo):
    cfg, model, params = olmo
    res = ag.decode_agreement(cfg, model, params, length=LENGTH, batch=BATCH,
                              serve_step=_drop_odd_cache_writes(cfg))
    assert res.agree < ag.AGREE_MIN, res
    assert res.control <= ag.CONTROL_MAX, res


def test_late_query_rotation_fails(olmo, monkeypatch):
    rope, calls = engine.apply_rope, itertools.count()

    def late_q(x, positions, theta):
        # each decode layer rotates q, then k: shift only q by one
        if next(calls) % 2 == 0:
            positions = positions + 1
        return rope(x, positions, theta)

    monkeypatch.setattr(engine, "apply_rope", late_q)
    res = ag.decode_agreement(*olmo, length=LENGTH, batch=BATCH)
    assert next(calls) > 0, "the serve step no longer calls apply_rope"
    assert res.agree < ag.AGREE_MIN, res
