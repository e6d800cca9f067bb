"""Model configuration and parameter-spec plumbing.

One :class:`ModelConfig` covers all ten assigned architectures; family-
specific fields are zero/empty when unused.  :class:`ParamSpec` records,
per parameter, which logical axis is tensor-parallel (sharded over the
``model`` mesh axis) and which is FSDP (sharded over ``data``); both the
shard_map ``in_specs`` and the GSPMD ``NamedSharding`` derive from it, so
there is exactly one source of truth for the layout.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


def _round_up(x: int, to: int) -> int:
    return ((x + to - 1) // to) * to


def shard_decisions(cfg: "ModelConfig") -> dict:
    """The single source of truth for what is TP-sharded: used by the
    parameter initializers (specs) AND the runtime TP plan, so layouts and
    compute plans can never disagree."""
    t = cfg.tp_target
    attn = cfg.n_heads > 0 and cfg.n_heads % t == 0
    kv = attn and cfg.n_kv_heads % t == 0
    ssm = cfg.ssm_state > 0 and (cfg.ssm_heads % t == 0)
    return {"attn": attn, "kv": kv, "ssm": ssm}


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | vlm | ssm | hybrid | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None

    # norms / MLP / block structure
    norm: str = "rmsnorm"            # rmsnorm | layernorm | layernorm_np
    mlp: str = "swiglu"              # swiglu | gelu | relu2
    parallel_block: bool = False     # attention & FFN in parallel (Cohere)
    tie_embeddings: bool = False
    qk_norm: bool = False
    rope_theta: float = 10_000.0

    # attention pattern
    sliding_window: int = 0          # 0 = full attention everywhere
    swa_every_nth_global: int = 0    # e.g. 6 => layers 5,11,... global (5:1)
    global_layers: Tuple[int, ...] = ()   # explicit global layers (hymba)

    # MoE
    n_experts: int = 0
    top_k: int = 0
    shared_expert_ff: int = 0
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    router_z_coef: float = 1e-3
    # "sigmoid": routing as DeepSeek-V3 (noaux_tc), sigmoid scores with a
    # per-expert bias that only selects; either way the top-k weights are
    # renormalized, then scaled by routed_scale
    router_score: str = "softmax"    # softmax | sigmoid
    routed_scale: float = 1.0
    # expert parallelism's share: this program holds routed experts
    # [first_expert, first_expert + experts_held) of n_experts (0 = all);
    # the router still scores all n_experts
    experts_held: int = 0
    first_expert: int = 0
    # leading dense layers (DeepSeek first_k_dense_replace) and their width
    first_dense_layers: int = 0
    dense_ff: int = 0

    # latent attention (MLA, DeepSeek-V2/V3): on when kv_lora_rank > 0.
    # K and V come from a kv_lora_rank latent (its own RMSNorm) through
    # per-head up-projections; a qk_rope_head_dim rotary key is shared by
    # the heads; no q_lora_rank (queries project from the residual)
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    norm_eps: Optional[float] = None  # None: each norm kind's default

    # SSM (Mamba2 SSD)
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 64
    ssm_conv_kernel: int = 4
    ssm_groups: int = 1

    # VLM / enc-dec frontends (stubs provide embeddings directly)
    cross_attn_every: int = 0        # every Nth layer cross-attends (vlm)
    n_image_tokens: int = 0
    encoder_layers: int = 0          # >0 => encoder-decoder (whisper)
    n_audio_frames: int = 0

    # numerics
    dtype: Any = jnp.bfloat16

    # the model-axis width the parameter layout targets (production mesh);
    # runtime meshes must divide the sharded dims identically
    tp_target: int = 16

    # ---------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.n_heads if self.n_heads else 0

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a multiple of 128 (MXU lane width & TP-divisible);
        padded logit slots are masked to -inf in the loss."""
        return _round_up(self.vocab, 128)

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def n_experts_held(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def n_stacked_layers(self) -> int:
        """The layers of the scanned stack: all after the leading dense
        ones."""
        return self.n_layers - self.first_dense_layers

    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.ssm_d_inner // self.ssm_headdim

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    def uses_subquadratic_attention(self) -> bool:
        """Eligible for the long_500k shape (DESIGN.md §4 shape skips)."""
        return (self.family in ("ssm", "hybrid")
                or self.sliding_window > 0)

    def layer_is_global(self, i: int) -> bool:
        """Does layer ``i`` use full (global) attention?"""
        if self.sliding_window == 0:
            return True
        if i in self.global_layers:
            return True
        if self.swa_every_nth_global:
            return (i + 1) % self.swa_every_nth_global == 0
        return False

    def _attn_params(self) -> int:
        d, dh = self.d_model, self.resolved_head_dim
        nq, nkv = self.n_heads, self.n_kv_heads
        if self.is_mla:
            r, dr = self.kv_lora_rank, self.qk_rope_head_dim
            dn, dv = self.qk_nope_head_dim, self.v_head_dim
            return (d * nq * (dn + dr) + d * (r + dr) + r
                    + r * nq * (dn + dv) + nq * dv * d)
        return d * (nq * dh) + 2 * d * (nkv * dh) + (nq * dh) * d

    def _expert_params(self) -> int:
        """One routed expert's weights."""
        ff_mult = 3 if self.mlp == "swiglu" else 2
        return ff_mult * self.d_model * self.d_ff

    def param_count(self) -> int:
        """Analytic parameter count (embedding included once if tied):
        attention (MLA or not), the experts this program holds, the
        router and its bias, the shared expert, leading dense layers."""
        d, dh = self.d_model, self.resolved_head_dim
        nq, nkv = self.n_heads, self.n_kv_heads
        ff_mult = 3 if self.mlp == "swiglu" else 2
        per_layer = 0
        if self.family != "ssm":
            per_layer += self._attn_params()
        if self.family in ("ssm", "hybrid"):
            di = self.ssm_d_inner
            per_layer += d * (2 * di + 2 * self.ssm_groups * self.ssm_state
                              + self.ssm_heads)
            per_layer += di * d + self.ssm_conv_kernel * di + 2 * self.ssm_heads
        dense_ffn = ff_mult * d * self.d_ff
        if self.n_experts:
            dense_ffn = ff_mult * d * self.dense_ff
            per_layer += self.n_experts_held * self._expert_params()
            per_layer += d * self.n_experts                    # router
            if self.router_score == "sigmoid":
                per_layer += self.n_experts                    # its bias
            if self.shared_expert_ff:
                per_layer += ff_mult * d * self.shared_expert_ff
        elif self.d_ff:
            per_layer += dense_ffn
        per_layer += 2 * d                                     # norms
        n_cross = 0
        if self.cross_attn_every:
            n_cross = self.n_layers // self.cross_attn_every
        cross = n_cross * (2 * d * (nq * dh) + 2 * d * (nkv * dh))
        emb = self.padded_vocab * d * (1 if self.tie_embeddings else 2)
        enc = self.encoder_layers * per_layer                  # (approx)
        # a leading dense layer: attention and norms as the rest, its own
        # FFN in place of the experts
        dense = self.first_dense_layers * (
            (self._attn_params() if self.family != "ssm" else 0)
            + dense_ffn + 2 * d)
        return (self.n_stacked_layers * per_layer + dense + cross + emb + enc
                + d)

    def active_param_count(self) -> int:
        """Activated parameters per token (MoE: top-k experts only; the
        top-k of the whole router, whatever share this program holds)."""
        if not self.n_experts:
            return self.param_count()
        per_expert = self.n_stacked_layers * self._expert_params()
        return self.param_count() + per_expert * (self.top_k
                                                  - self.n_experts_held)


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Layout metadata for one parameter (per-layer shape, pre-stacking).

    ``tp_axis``   — dim sharded over the ``model`` mesh axis (None = replicated).
    ``fsdp_axis`` — dim sharded over ``data`` at rest (None = replicated);
                    gathered by ``Comm.weight`` right before use.
    ``stacked``   — True for per-layer params stored as (L, ...) under scan;
                    mesh dims shift right by one.
    """
    tp_axis: Optional[int] = None
    fsdp_axis: Optional[int] = None
    stacked: bool = True

    def pspec(self, *, model_axis="model", data_axis="data",
              stacked: Optional[bool] = None, ndim: Optional[int] = None):
        """PartitionSpec for shard_map in_specs / GSPMD NamedSharding."""
        from jax.sharding import PartitionSpec as P
        st = self.stacked if stacked is None else stacked
        off = 1 if st else 0
        set_axes = [a for a in (self.tp_axis, self.fsdp_axis)
                    if a is not None]
        if not set_axes:
            return P()                       # fully replicated, any rank
        n = ndim if ndim is not None else 1 + max(set_axes)
        dims: list = [None] * (n + off)
        if self.tp_axis is not None:
            dims[self.tp_axis + off] = model_axis
        if self.fsdp_axis is not None:
            dims[self.fsdp_axis + off] = data_axis
        return P(*dims)


def truncated_normal_init(key, shape, scale: float, dtype) -> jax.Array:
    stddev = scale / math.sqrt(shape[0] if len(shape) > 1 else 1.0)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * stddev).astype(dtype)


class ParamFactory:
    """Init-time helper that records a ParamSpec for every created param."""

    def __init__(self, key: jax.Array, dtype):
        self._key = key
        self.dtype = dtype
        self.specs: Dict[str, ParamSpec] = {}

    def next_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def dense(self, name: str, shape: Tuple[int, ...], *,
              tp_axis: Optional[int], fsdp_axis: Optional[int],
              stacked: bool = True, scale: float = 1.0) -> jax.Array:
        self.specs[name] = ParamSpec(tp_axis, fsdp_axis, stacked)
        return truncated_normal_init(self.next_key(), shape, scale,
                                     self.dtype)

    def zeros(self, name: str, shape: Tuple[int, ...], *,
              tp_axis: Optional[int] = None,
              fsdp_axis: Optional[int] = None, stacked: bool = True,
              dtype=None) -> jax.Array:
        self.specs[name] = ParamSpec(tp_axis, fsdp_axis, stacked)
        return jnp.zeros(shape, dtype or self.dtype)

    def ones(self, name: str, shape: Tuple[int, ...], *,
             tp_axis: Optional[int] = None,
             fsdp_axis: Optional[int] = None, stacked: bool = True,
             dtype=None) -> jax.Array:
        self.specs[name] = ParamSpec(tp_axis, fsdp_axis, stacked)
        return jnp.ones(shape, dtype or self.dtype)
