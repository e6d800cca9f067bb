"""Production meshes and the shard_map step builders.

Mesh shapes (DESIGN.md §5):

* single-pod: ``(16, 16)`` over ``("data", "model")`` — 256 chips (one
  TPU v5e pod slice).  ``data`` carries DP + FSDP, ``model`` carries
  TP/EP/SP.
* multi-pod: ``(2, 16, 16)`` over ``("pod", "data", "model")`` — 512
  chips; ``pod`` is an extra pure-DP axis (gradients cross pods once per
  step, hierarchically: AD's reduce over ``data`` first, then the ring
  over ``pod`` on already-reduced shards).

``make_production_mesh`` is a FUNCTION (not a module constant) so that
importing this module never touches jax device state.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core.modes import CommConfig, CommMode
from repro.core.progress import EndpointSpec
from repro.distributed.comm import Comm


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              devices: Optional[Sequence[Any]] = None) -> Mesh:
    """``jax.make_mesh`` with every axis in Auto mode: the steps are
    written for manual ``shard_map`` SPMD under jit-propagated
    shardings, not for jax's default Explicit axes."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         (AxisType.Auto,) * len(axis_names), devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def make_comm(mesh: Mesh, config: Optional[CommConfig] = None, *,
              endpoint: Optional[EndpointSpec] = None) -> Comm:
    """Build the step Comm; ``endpoint`` picks the resource bundle the
    step's collectives ride (its width becomes the channel count)."""
    return Comm(config or CommConfig(), model_axis="model",
                data_axis=data_axes(mesh), endpoint=endpoint)


def shard(mesh: Mesh, tree_pspecs):
    """pspec pytree -> NamedSharding pytree."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree_pspecs,
        is_leaf=lambda x: isinstance(x, P))


def batch_pspecs(cfg, shape_kind: str, mesh: Mesh, *, batch: int
                 ) -> Dict[str, P]:
    """PartitionSpecs for the batch dict of one cell."""
    daxes = data_axes(mesh)
    if shape_kind == "decode":
        tok = P() if batch == 1 else P(daxes)
        out = {"tokens": tok}
    else:
        out = {"tokens": P("model", daxes), "labels": P("model", daxes)}
    if cfg.family == "vlm":
        out["image_embeds"] = P(None, daxes if batch > 1 else None, None)
    if cfg.is_encdec:
        out["frames"] = P("model", daxes if batch > 1 else None, None)
    if shape_kind == "decode":
        out.pop("labels", None)
    return out
