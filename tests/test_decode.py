"""Serving engine: teacher-forced decode must reproduce the training
forward's next-token predictions, for every family; the K/V rows it
writes; the layer scan's cache traffic under its named scope; plus the
paged allocator and the continuous-batching scheduler."""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.completion import CompletionQueue
from repro.distributed.comm import local_comm
from repro.models.common import ModelConfig
from repro.models.layers import greedy_sample, lm_head_logits
from repro.configs import get_smoke
from repro.models.registry import build_model
from repro.serving import PagedKVAllocator, ServeScheduler
from repro.serving import engine
from repro.serving.engine import (CACHE_IO, DecodeCache, init_cache,
                                  make_serve_step, precompute_cross_kv)

F = jnp.float32
S, B = 16, 2


def _decode_inputs(cfg, extra=None, n_mem=0):
    """Params, teacher-forced tokens (S, B), the batch and an empty cache
    (with the memory KV for enc-dec and VLM)."""
    m = build_model(cfg)
    params, _ = m.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (S, B), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    if extra:
        batch.update(extra)
    comm = local_comm()
    cache = init_cache(cfg, S, B, n_memory=n_mem)
    if n_mem:
        if cfg.is_encdec:
            from repro.models import lm as lm_mod
            from repro.models.blocks import tp_plan
            mem = lm_mod._encode(params, batch, cfg, comm, tp_plan(cfg, 1),
                                 remat=False)
        else:
            mem = extra["image_embeds"]
        ck, cv = precompute_cross_kv(params, mem, cfg, comm)
        cache = DecodeCache(k=cache.k, v=cache.v, ssm_state=cache.ssm_state,
                            conv_tail=cache.conv_tail, cross_k=ck,
                            cross_v=cv, length=cache.length)
    return m, params, tokens, batch, cache


def _agreement(cfg, extra=None, n_mem=0):
    m, params, tokens, batch, cache = _decode_inputs(cfg, extra, n_mem)
    comm = local_comm()
    x, _ = jax.jit(lambda p, bt: m.forward(p, bt, remat=False))(params,
                                                                batch)
    head = params.get("lm_head", params["emb"])
    oracle = jax.vmap(lambda xp: greedy_sample(
        lm_head_logits(xp, head, comm, real_vocab=cfg.vocab), comm))(x)
    step = jax.jit(make_serve_step(cfg))
    preds = []
    for i in range(S):
        nxt, cache = step(params, cache, tokens[i])
        preds.append(np.asarray(nxt))
    return (np.stack(preds) == np.asarray(oracle)).mean()


CASES = {
    "dense": (ModelConfig(name="dense", family="dense", n_layers=2,
                          d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                          vocab=128, tp_target=4, dtype=F), None, 0),
    "parallel": (ModelConfig(name="parallel", family="dense", n_layers=2,
                             d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
                             vocab=128, tp_target=4, dtype=F,
                             norm="layernorm", parallel_block=True,
                             tie_embeddings=True), None, 0),
    "swa-qk": (ModelConfig(name="swa-qk", family="dense", n_layers=3,
                           d_model=64, n_heads=2, n_kv_heads=1, d_ff=128,
                           vocab=128, tp_target=4, dtype=F, head_dim=32,
                           sliding_window=6, swa_every_nth_global=3,
                           qk_norm=True), None, 0),
    "moe": (ModelConfig(name="moe", family="moe", n_layers=2, d_model=64,
                        n_heads=4, n_kv_heads=4, d_ff=96, vocab=128,
                        n_experts=8, top_k=2, tp_target=4, dtype=F,
                        capacity_factor=8.0, shared_expert_ff=64), None, 0),
    "ssm": (ModelConfig(name="ssm", family="ssm", n_layers=2, d_model=64,
                        n_heads=0, n_kv_heads=0, d_ff=0, vocab=128,
                        ssm_state=16, ssm_headdim=16, ssm_chunk=8,
                        tp_target=4, dtype=F), None, 0),
    "hybrid": (ModelConfig(name="hybrid", family="hybrid", n_layers=2,
                           d_model=64, n_heads=5, n_kv_heads=5, d_ff=128,
                           vocab=128, ssm_state=8, ssm_headdim=16,
                           ssm_chunk=8, tp_target=4, dtype=F, head_dim=16,
                           sliding_window=6, global_layers=(0,)), None, 0),
    "vlm": (ModelConfig(name="vlm", family="vlm", n_layers=4, d_model=64,
                        n_heads=4, n_kv_heads=2, d_ff=128, vocab=128,
                        cross_attn_every=2, tp_target=4, dtype=F),
            {"image_embeds": jax.random.normal(jax.random.PRNGKey(5),
                                               (8, B, 64), F)}, 8),
    "whisper": (ModelConfig(name="whisper", family="audio", n_layers=2,
                            d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
                            vocab=128, norm="layernorm", mlp="gelu",
                            encoder_layers=2, tp_target=4, dtype=F,
                            tie_embeddings=True),
                {"frames": jax.random.normal(jax.random.PRNGKey(6),
                                             (8, B, 64), F)}, 8),
}


@pytest.mark.parametrize("name", list(CASES))
def test_decode_matches_forward(name):
    cfg, extra, n_mem = CASES[name]
    assert _agreement(cfg, extra, n_mem) > 0.95


def _layer_slice_write(buf, row, idx, at, owns):
    """The plain reference of the step's K/V write: over the cache in
    ``(L, S, B, n_kv, dh)`` order, slice layer ``idx`` out, set row ``at``
    (or write back the row read, on a shard that does not own it), and
    set the whole layer back; given and returned in the cache's order."""
    old = buf.transpose(0, 1, 3, 2, 4)
    layer = old[idx]
    layer = layer.at[at].set(jnp.where(owns, row.swapaxes(0, 1).astype(
        buf.dtype), layer[at]))
    return old.at[idx].set(layer).transpose(0, 1, 3, 2, 4)


@pytest.mark.parametrize("name", ["dense", "moe", "hybrid", "whisper",
                                  "vlm"])
def test_cache_rows_match_the_layer_slice_write(name, monkeypatch):
    """After N < S steps, layer l's row p holds, for every p < N, what
    the layer-slice write puts there; rows p >= N are zero; the tokens
    are the same."""
    cfg, extra, n_mem = CASES[name]
    _, params, tokens, _, cache0 = _decode_inputs(cfg, extra, n_mem)
    n = S - 5

    def decode():
        step, cache, out = jax.jit(make_serve_step(cfg)), cache0, []
        for i in range(n):
            nxt, cache = step(params, cache, tokens[i])
            out.append(np.asarray(nxt))
        return np.stack(out), cache

    toks, got = decode()
    monkeypatch.setattr(engine, "_write_row", _layer_slice_write)
    ref_toks, want = decode()
    np.testing.assert_array_equal(toks, ref_toks)
    layers = cache0.k.shape[0]
    for a, b in ((got.k, want.k), (got.v, want.v)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == (layers, S, cfg.n_kv_heads, B,
                           cfg.resolved_head_dim)
        np.testing.assert_array_equal(a, b)
        assert a[:, :n].any(axis=(2, 3, 4)).all()
        assert not a[:, n:].any()


class TestPagedAllocator:
    def test_admit_extend_release(self):
        alloc = PagedKVAllocator(n_pages=8, page_size=4)
        st = alloc.admit(1, prompt_len=10)        # needs 3 pages
        assert st.is_done() and alloc.free_pages == 5
        assert alloc.extend(1, 16).is_done()      # grow to 4 pages
        assert alloc.free_pages == 4
        alloc.release(1)
        assert alloc.free_pages == 8

    def test_all_or_nothing_admission(self):
        alloc = PagedKVAllocator(n_pages=2, page_size=4)
        assert alloc.admit(1, 8).is_done()
        st = alloc.admit(2, 8)                    # no pages left
        assert st.is_retry()
        assert alloc.free_pages == 0              # no partial reservation

    def test_page_table_lookup(self):
        alloc = PagedKVAllocator(n_pages=4, page_size=4)
        alloc.admit(7, 8)
        table = alloc.tables[7]
        page, off = table.slot_of(5)
        assert off == 1 and page == table.pages[1]


class TestScheduler:
    def _engine(self):
        # fake decode: next token = token + 1
        def decode_fn(tokens, positions):
            return tokens + 1
        return decode_fn

    def test_continuous_batching_completes(self):
        alloc = PagedKVAllocator(n_pages=64, page_size=4)
        sched = ServeScheduler(self._engine(), max_batch=4, allocator=alloc)
        cq = CompletionQueue()
        for i in range(10):
            st = sched.submit(np.array([i]), max_new=3, comp=cq,
                              allow_retry=False)
            assert not st.is_retry()
        rounds = 0
        while sched.completed < 10:
            sched.step()
            rounds += 1
            assert rounds < 100
        outs = []
        while True:
            st = cq.pop()
            if st.is_retry():
                break
            outs.append(st.get_buffer())
        assert len(outs) == 10
        assert all(len(o) == 3 for o in outs)

    def test_backlog_under_page_pressure(self):
        alloc = PagedKVAllocator(n_pages=4, page_size=4)   # tiny
        sched = ServeScheduler(self._engine(), max_batch=8,
                               allocator=alloc)
        sts = [sched.submit(np.array([1, 2]), max_new=4, allow_retry=False)
               for _ in range(6)]
        assert any(s.code.name == "POSTED_BACKLOG" for s in sts)
        rounds = 0
        while sched.completed < 6:
            sched.step()
            rounds += 1
            assert rounds < 200
        assert sched.completed == 6
        assert alloc.free_pages == 4


# ---------------------------------------------------------------------------
# the cache_io scope: metadata on the layer scan's cache reads and writes
# ---------------------------------------------------------------------------

SCOPED = ["olmo-1b", "mamba2-370m"]
_INSTR = re.compile(r"^\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* "
                    r"([\w-]+)\(.*?(?:op_name=\"([^\"]*)\")?[^\"]*$")


def _smoke_hlo(arch):
    """The CPU-optimized HLO text of the smoke config's serve step."""
    cfg = get_smoke(arch)
    params = build_model(cfg).abstract_params()[0]
    cache = jax.eval_shape(lambda: init_cache(cfg, 16, 4))
    text = jax.jit(make_serve_step(cfg), donate_argnums=(1,)).lower(
        params, cache, jnp.zeros((4,), jnp.int32)).compile().as_text()
    return text, cache


def _strip(hlo):
    """Instructions and computation headers without their metadata (op
    names, source lines): the stack-frame tables go too."""
    return [re.sub(r", metadata=\{[^}]*\}", "", line)
            for line in hlo.splitlines()
            if line.lstrip().startswith(("%", "ROOT", "ENTRY", "}"))]


@pytest.mark.parametrize("arch", SCOPED)
def test_cache_io_scope_is_on_every_cache_slice_and_update(arch):
    hlo, cache = _smoke_hlo(arch)
    bufs = [a.shape for a in (cache.k, cache.v, cache.ssm_state,
                              cache.conv_tail) if a is not None]
    whole = {",".join(map(str, s)) for s in bufs}
    layer = {",".join(map(str, (1,) + s[1:])) for s in bufs}
    seen = {"dynamic-slice": 0, "dynamic-update-slice": 0}
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        dims, op, name = m.groups()
        if (op == "dynamic-slice" and dims in layer) or (
                op == "dynamic-update-slice" and dims in whole):
            seen[op] += 1
            assert name and CACHE_IO in name.split("/"), line
    assert all(seen.values()), seen


@pytest.mark.parametrize("arch", SCOPED)
def test_cache_io_scope_changes_metadata_only(arch, monkeypatch):
    scoped, _ = _smoke_hlo(arch)
    assert f"/{CACHE_IO}/" in scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain, _ = _smoke_hlo(arch)
    assert f"/{CACHE_IO}/" not in plain
    assert _strip(scoped) == _strip(plain)
