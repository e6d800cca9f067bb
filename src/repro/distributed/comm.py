"""The ``Comm`` object — how model code talks to the LCI-X layer.

Model code is written in *local view* (the shapes one device sees inside
``shard_map``), and every data movement goes through a :class:`Comm`, which
is the in-graph analogue of an LCI *device*: a full set of communication
resources the caller posts operations to.  Two deployments of the same
model code:

* **local** (``local_comm()``) — no mesh axes; every collective degenerates
  to its local computation.  Used by CPU smoke tests and single-chip runs.
* **shard_map manual** — axes bound; collectives lower to the explicit ring
  schedules of :mod:`repro.core.collectives` in the mode picked by
  ``CommConfig`` (BSP = paper's bulk-synchronous baseline, LCI_* = the
  paper's contribution).

Axis conventions (DESIGN.md §5): ``model`` = TP/EP/SP axis; ``data`` =
DP/FSDP axis (a tuple like ``("pod", "data")`` on the multi-pod mesh).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from jax.lax import axis_size

from repro.core import attrs as _attrs
from repro.core import collectives as C
from repro.core.modes import CommConfig, CommMode
from repro.core.progress import EndpointSpec

AxisSpec = Union[str, Tuple[str, ...], None]


def _axes(a: AxisSpec) -> Tuple[str, ...]:
    if a is None:
        return ()
    return (a,) if isinstance(a, str) else tuple(a)


@dataclasses.dataclass(frozen=True)
class Comm:
    """In-graph communication device handed to model code."""

    config: CommConfig
    model_axis: AxisSpec = None
    data_axis: AxisSpec = None
    # Endpoint spec: which resource bundle this Comm's collectives ride.
    # On the host runtime an EndpointSpec materializes as N devices; in
    # the in-graph layer the same knob selects the collective channel
    # count (chunk-streams) and the shared/dedicated schedule mode.
    endpoint: Optional[EndpointSpec] = None

    @property
    def cfg(self) -> CommConfig:
        """The CommConfig collectives actually run with: the endpoint spec
        overrides channel count and mode (BSP is never overridden — the
        baseline stays the baseline)."""
        if self.endpoint is None or self.config.mode == CommMode.BSP:
            return self.config
        # the progress policy alone picks the mode: a shared multi-device
        # spec stays LCI_SHARED (one chunk-stream), exactly as
        # EndpointSpec.for_mode round-trips it
        mode = (CommMode.LCI_DEDICATED
                if self.endpoint.progress == "dedicated"
                else CommMode.LCI_SHARED)
        return dataclasses.replace(self.config, mode=mode,
                                   n_channels=self.endpoint.n_devices)

    def with_endpoint(self, spec: EndpointSpec) -> "Comm":
        return dataclasses.replace(self, endpoint=spec)

    # -- attribute introspection (DESIGN.md §12): the Comm is a view over
    #    the effective config its collectives actually run with ----------
    def get_attr(self, name: str):
        """Query one attribute of the *effective* config (endpoint spec
        layered over the CommConfig), plus the discovered mesh widths
        ``tp``/``dp``.  Endpoint attrs (``stripe``/``progress``/
        ``n_devices``/...) resolve against the attached spec."""
        name = _attrs.canonical_name(name)
        if name == "tp":
            return self.tp
        if name == "dp":
            return self.dp
        if self.endpoint is not None:
            try:
                return self.endpoint.get_attr(name)
            except _attrs.AttrError:
                pass                       # not an endpoint attr: fall back
        return self.cfg.get_attr(name)

    @property
    def attrs(self) -> dict:
        out = dict(self.cfg.attrs)
        if self.endpoint is not None:
            out.update(self.endpoint.attrs)
        return out

    # -- axis sizes (1 when unbound) ----------------------------------------
    @property
    def tp(self) -> int:
        return math.prod([axis_size(a)
                          for a in _axes(self.model_axis)] or [1])

    @property
    def dp(self) -> int:
        return math.prod([axis_size(a)
                          for a in _axes(self.data_axis)] or [1])

    def _one_model_axis(self) -> Optional[str]:
        ax = _axes(self.model_axis)
        if len(ax) > 1:
            raise ValueError("model axis must be a single mesh axis")
        return ax[0] if ax else None

    # -- tensor-parallel matmuls (SP <-> TP boundary) ------------------------
    def ag_matmul(self, x: jax.Array, w: jax.Array) -> jax.Array:
        """``allgather(x, axis=0 over model) @ w`` — column-parallel entry.
        x: (s_local, ..., k) seq-sharded; w: (k, n_local)."""
        ax = self._one_model_axis()
        if ax is None:
            return jnp.tensordot(x, w, axes=1).astype(x.dtype)
        return C.all_gather_matmul(x, w, ax, self.cfg)

    def matmul_rs(self, x: jax.Array, w: jax.Array) -> jax.Array:
        """``reduce_scatter(x @ w, axis=0 over model)`` — row-parallel exit.
        x: (s, ..., k_local); w: (k_local, n).  Returns (s/TP, ..., n)."""
        ax = self._one_model_axis()
        if ax is None:
            return jnp.tensordot(x, w, axes=1).astype(x.dtype)
        return C.matmul_reduce_scatter(x, w, ax, self.cfg)

    def matmul_ar(self, x: jax.Array, w: jax.Array) -> jax.Array:
        """``allreduce(x @ w)`` — row-parallel exit without SP (decode path
        where s is tiny and scattering it is not possible)."""
        ax = self._one_model_axis()
        y = jnp.tensordot(x, w, axes=1).astype(x.dtype)
        if ax is None:
            return y
        return lax.psum(y, ax)

    # -- raw collectives over the model axis ---------------------------------
    def ag_seq(self, x: jax.Array, *, axis: int = 0) -> jax.Array:
        """All-gather the SP (sequence) dim back to full length."""
        ax = self._one_model_axis()
        if ax is None:
            return x
        return C.all_gather(x, ax, self.cfg, axis=axis)

    def rs_seq(self, x: jax.Array, *, axis: int = 0) -> jax.Array:
        ax = self._one_model_axis()
        if ax is None:
            return x
        return C.reduce_scatter(x, ax, self.cfg, axis=axis)

    def psum_model(self, x: jax.Array) -> jax.Array:
        ax = self._one_model_axis()
        if ax is None:
            return x
        return lax.psum(x, ax)

    def psum_model_ge(self, x: jax.Array) -> jax.Array:
        """Gradient-exact psum over the model axis.

        Under ``shard_map(check_vma=False)`` the transpose of ``psum`` is
        ``psum``, which overcounts cotangents by the axis size when the
        consumer (the loss) is *replicated* across the axis.  For that
        replicated-consumer case the exact transpose is identity: each
        rank's operand enters the sum with coefficient one.  Forward value
        is the psum; backward passes the cotangent through untouched::

            y = x + stop_gradient(psum(x) - x)

        Use this (not psum_model) on every differentiable reduction that
        feeds the replicated loss (vocab-parallel CE, SSM norm stats,
        router aux means) — tests/helpers/dist_equivalence.py asserts the
        resulting distributed grads equal the single-device oracle.
        """
        ax = self._one_model_axis()
        if ax is None:
            return x
        return x + lax.stop_gradient(lax.psum(x, ax) - x)

    def pmax_model(self, x: jax.Array) -> jax.Array:
        ax = self._one_model_axis()
        if ax is None:
            return x
        return lax.pmax(x, ax)

    def a2a(self, x: jax.Array, *, split_axis: int, concat_axis: int
            ) -> jax.Array:
        """All-to-all over the model axis (MoE dispatch/combine)."""
        ax = self._one_model_axis()
        if ax is None:
            return x
        return C.all_to_all(x, ax, split_axis=split_axis,
                            concat_axis=concat_axis, config=self.cfg)

    def model_index(self) -> jax.Array:
        ax = self._one_model_axis()
        if ax is None:
            return jnp.zeros((), jnp.int32)
        return lax.axis_index(ax)

    # -- FSDP (data axis) weight gather --------------------------------------
    def weight(self, w: jax.Array, *, fsdp_axis: Optional[int]) -> jax.Array:
        """Gather a weight's FSDP-sharded dim back to full size.

        This is the zero-copy bulk-transfer path (rendezvous protocol): in
        LCI modes it is a chunked ppermute ring whose steps XLA overlaps
        with the previous layer's compute; its VJP is the matching ring
        reduce(-scatter) of the weight gradient.
        """
        if fsdp_axis is None:
            return w
        axes = _axes(self.data_axis)
        if not axes:
            return w
        for a in reversed(axes):          # innermost axis gathered first
            w = C.all_gather(w, a, self.cfg, axis=fsdp_axis)
        return w

    # -- data-parallel reductions (loss/grad sync) ----------------------------
    def psum_data(self, x: jax.Array) -> jax.Array:
        axes = _axes(self.data_axis)
        for a in axes:
            x = lax.psum(x, a)
        return x

    def pmean_data(self, x: jax.Array) -> jax.Array:
        axes = _axes(self.data_axis)
        if not axes:
            return x
        return jax.tree_util.tree_map(
            lambda v: self.psum_data(v) / self.dp, x)

    def psum_all(self, x: jax.Array) -> jax.Array:
        return self.psum_model(self.psum_data(x))

    def pmean_all(self, x: jax.Array) -> jax.Array:
        """Mean over every mesh axis — makes a metric fully replicated."""
        n = self.tp * self.dp
        return jax.tree_util.tree_map(
            lambda v: self.psum_all(v) / n, x)

    # -- barrier (paper §6 primitive, used by the launcher) -------------------
    def barrier(self) -> jax.Array:
        ax = self._one_model_axis()
        tok = jnp.ones((), jnp.int32)
        if ax is not None:
            tok = C.dissemination_barrier(ax)
        for a in _axes(self.data_axis):
            tok = tok * 0 + C.dissemination_barrier(a)
        return tok


def local_comm(config: Optional[CommConfig] = None) -> Comm:
    """A Comm with no mesh axes: collectives degenerate to local compute."""
    return Comm(config or CommConfig(), model_axis=None, data_axis=None)
