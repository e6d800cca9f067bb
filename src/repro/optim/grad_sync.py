"""Gradient synchronization under manual SPMD — the subtle part.

Inside ``shard_map``, reverse-mode AD already does *some* of the gradient
reduction for us, because the forward collectives have exact transposes:

* FSDP-dim params (``fsdp_axis`` set): the forward ``all_gather`` over data
  transposes to a reduce(-scatter) — the shard's grad arrives **already
  summed over the data axis**.
* TP-sharded params (``tp_axis`` set): each model rank's shard grad is its
  own — nothing to reduce over the model axis.
* *Replicated* dims are the ones AD cannot see: a weight used identically
  by every rank of an axis needs an explicit psum of its grad over that
  axis.

``grad_sync`` applies exactly the missing reductions, per ParamSpec, and
normalizes to the **mean over data shards**.  Getting this wrong is silent
(loss still goes down, just wrong) — tests/test_train.py checks
distributed grads == single-device grads for every family.

In LCI modes the data-axis reductions lower to the ring schedules of
:mod:`repro.core.collectives` (chunk streams the XLA scheduler overlaps
with the backward compute of the *next* layer — the paper's
computation/communication overlap at the gradient level).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from jax.lax import axis_size

from repro.core import collectives as C
from repro.distributed.comm import Comm, _axes
from repro.models.common import ParamSpec


def _psum_data(x: jax.Array, comm: Comm) -> jax.Array:
    for a in _axes(comm.data_axis):
        if x.ndim >= 1 and x.shape[0] % axis_size(a) == 0:
            x = C.all_reduce(x, a, comm.cfg)        # ring rs+ag in LCI modes
        else:
            x = jax.lax.psum(x, a)
    return x


def grad_sync(grads: Dict[str, Any], specs: Dict[str, Any], comm: Comm
              ) -> Dict[str, Any]:
    """Apply the missing reductions; result = mean over data shards."""
    dp = comm.dp

    def sync(g: jax.Array, spec: ParamSpec) -> jax.Array:
        if spec.tp_axis is None:
            g = comm.psum_model(g)
        if spec.fsdp_axis is None:
            g = _psum_data(g, comm)
        return (g / dp).astype(g.dtype)

    return jax.tree_util.tree_map(sync, grads, specs)


def global_norm(grads: Dict[str, Any], specs: Dict[str, Any], comm: Comm
                ) -> jax.Array:
    """Global L2 norm of the (synced) gradient across all shards.

    Replicated dims would be double-counted by a blind psum; each param's
    local sum-of-squares is weighted by 1/replication before the reduce.
    """
    tp, dp = comm.tp, comm.dp
    total = jnp.zeros((), jnp.float32)
    for g, spec in zip(jax.tree_util.tree_leaves(grads),
                       jax.tree_util.tree_leaves(
                           specs, is_leaf=lambda x: isinstance(x, ParamSpec))):
        w = 1.0
        if spec.tp_axis is None:
            w /= tp
        if spec.fsdp_axis is None:
            w /= dp
        gf = g.astype(jnp.float32)
        total = total + w * jnp.sum(gf * gf)
    return jnp.sqrt(comm.psum_all(total))


def clip_by_global_norm(grads, specs, comm: Comm, max_norm: float):
    gn = global_norm(grads, specs, comm)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-9))
    return (jax.tree_util.tree_map(
        lambda g: (g * scale).astype(g.dtype), grads), gn)
