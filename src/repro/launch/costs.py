"""Jaxpr-walking cost model — trip-count-exact FLOPs / bytes / collectives.

XLA's ``compiled.cost_analysis()`` counts a ``scan`` (while-loop) body
ONCE, so any scan-rolled program (all of ours: layer stacks, flash blocks,
CE chunks) is undercounted by the trip count.  This walker recurses the
jaxpr instead, multiplying by static scan lengths — exact for this
framework's programs (no data-dependent while loops on the hot path).

Per-device accounting (walk the jaxpr of the *shard_mapped* function:
inner shapes are local shapes):

* ``flops``            — 2·batch·m·n·k per dot_general (einsums included);
* ``dot_bytes``        — Σ (lhs+rhs+out) bytes of every dot: the HBM-traffic
  model for a well-fused program (weights streamed per scan iteration are
  dot operands, so FSDP/TP weight streaming is captured exactly);
* ``collective``       — per-kind transferred bytes using ring algorithm
  models; ppermute bytes split by ring *direction* (the two ICI links),
  with per-direction serial step counts (latency-chain proxy).

Ring models (bytes one device puts on a link, per op):
  ppermute: |operand|;  all_gather(tiled): |in|·(P-1);
  reduce_scatter: |out|·(P-1);  psum: 2·|x|·(P-1)/P;
  all_to_all: |x|·(P-1)/P;  pmax/pmin: like psum.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import numpy as np


@dataclasses.dataclass
class Costs:
    flops: float = 0.0
    dot_bytes: float = 0.0
    coll_bytes: Dict[str, float] = dataclasses.field(default_factory=dict)
    ppermute_fwd_bytes: float = 0.0
    ppermute_bwd_bytes: float = 0.0
    ppermute_fwd_steps: float = 0.0
    ppermute_bwd_steps: float = 0.0
    unknown_while: int = 0

    @property
    def total_coll_bytes(self) -> float:
        return sum(self.coll_bytes.values())

    @property
    def link_bytes(self) -> float:
        """Worst single-link traffic: counter-rotating rings use both
        directions concurrently, so the busier direction + everything
        that is not direction-split."""
        other = self.total_coll_bytes - self.ppermute_fwd_bytes \
            - self.ppermute_bwd_bytes
        return max(self.ppermute_fwd_bytes, self.ppermute_bwd_bytes) + other

    def as_dict(self) -> Dict[str, Any]:
        return {
            "flops": self.flops,
            "dot_bytes": self.dot_bytes,
            "coll_bytes_by_kind": dict(self.coll_bytes),
            "coll_bytes_total": self.total_coll_bytes,
            "coll_link_bytes": self.link_bytes,
            "ppermute_fwd_bytes": self.ppermute_fwd_bytes,
            "ppermute_bwd_bytes": self.ppermute_bwd_bytes,
            "ppermute_fwd_steps": self.ppermute_fwd_steps,
            "ppermute_bwd_steps": self.ppermute_bwd_steps,
            "unknown_while": self.unknown_while,
        }


def _nbytes(aval) -> int:
    try:
        return int(np.prod(aval.shape)) * aval.dtype.itemsize
    except Exception:
        return 0


def _axes_prod(axes, axis_sizes: Dict[str, int]) -> int:
    if isinstance(axes, str):
        axes = (axes,)
    return math.prod(axis_sizes.get(a, 1) for a in axes)


def _sub_jaxprs(params: Dict[str, Any]):
    for v in params.values():
        if isinstance(v, (list, tuple)):
            for x in v:
                if hasattr(x, "eqns"):
                    yield x
                elif hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                    yield x.jaxpr
        elif hasattr(v, "eqns"):
            yield v
        elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
            yield v.jaxpr


def count_costs(jaxpr, axis_sizes: Dict[str, int],
                costs: Optional[Costs] = None, mult: float = 1.0) -> Costs:
    """Walk a (Closed)Jaxpr; multiply scan bodies by their length."""
    c = costs if costs is not None else Costs()
    jx = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr

    for eqn in jx.eqns:
        name = eqn.primitive.name
        p = eqn.params

        if name == "dot_general":
            (lc, rc), (lb, rb) = p["dimension_numbers"]
            lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
            contract = math.prod(lhs.shape[i] for i in lc) or 1
            batch = math.prod(lhs.shape[i] for i in lb) or 1
            lfree = math.prod(lhs.shape[i] for i in range(len(lhs.shape))
                              if i not in lc and i not in lb) or 1
            rfree = math.prod(rhs.shape[i] for i in range(len(rhs.shape))
                              if i not in rc and i not in rb) or 1
            c.flops += mult * 2.0 * batch * lfree * rfree * contract
            c.dot_bytes += mult * (_nbytes(lhs) + _nbytes(rhs)
                                   + sum(_nbytes(v.aval)
                                         for v in eqn.outvars))
            continue

        if name == "ppermute":
            b = _nbytes(eqn.invars[0].aval) * mult
            perm = p.get("perm", ())
            fwd = True
            if perm:
                src, dst = perm[0]
                n = max(max(s, d) for s, d in perm) + 1
                fwd = dst == (src + 1) % n
            c.coll_bytes["ppermute"] = c.coll_bytes.get("ppermute", 0.0) + b
            if fwd:
                c.ppermute_fwd_bytes += b
                c.ppermute_fwd_steps += mult
            else:
                c.ppermute_bwd_bytes += b
                c.ppermute_bwd_steps += mult
            continue

        if name in ("psum", "psum_invariant", "pmax", "pmin"):
            pp = _axes_prod(p.get("axes", ()), axis_sizes)
            b = sum(_nbytes(v.aval) for v in eqn.invars)
            xfer = 2.0 * b * (pp - 1) / max(pp, 1) * mult
            key = "psum" if name.startswith("psum") else name
            c.coll_bytes[key] = c.coll_bytes.get(key, 0.0) + xfer
            continue

        if name == "all_gather":
            pp = p.get("axis_size", _axes_prod(p.get("axis_name", ()),
                                               axis_sizes))
            b = _nbytes(eqn.invars[0].aval)
            xfer = b * (pp - 1) * mult
            c.coll_bytes["all_gather"] = \
                c.coll_bytes.get("all_gather", 0.0) + xfer
            continue

        if name == "reduce_scatter":
            pp = p.get("axis_size", 1)
            b = sum(_nbytes(v.aval) for v in eqn.outvars)
            xfer = b * (pp - 1) * mult
            c.coll_bytes["reduce_scatter"] = \
                c.coll_bytes.get("reduce_scatter", 0.0) + xfer
            continue

        if name == "all_to_all":
            pp = p.get("axis_size", _axes_prod(p.get("axis_name", ()),
                                               axis_sizes))
            b = _nbytes(eqn.invars[0].aval)
            xfer = b * (pp - 1) / max(pp, 1) * mult
            c.coll_bytes["all_to_all"] = \
                c.coll_bytes.get("all_to_all", 0.0) + xfer
            continue

        if name == "scan":
            count_costs(p["jaxpr"], axis_sizes, c,
                        mult * float(p.get("length", 1)))
            continue

        if name == "pallas_call":
            # the kernel body runs once per grid step
            count_costs(p["jaxpr"], axis_sizes, c,
                        mult * math.prod(p["grid_mapping"].grid))
            continue

        if name == "while":
            c.unknown_while += 1
            for sub in _sub_jaxprs(p):
                count_costs(sub, axis_sizes, c, mult)
            continue

        if name == "cond":
            # conservative: count the most expensive branch
            best, best_fl = None, -1.0
            for sub in _sub_jaxprs(p):
                probe = count_costs(sub, axis_sizes, Costs(), mult)
                if probe.flops > best_fl:
                    best, best_fl = probe, probe.flops
            if best is not None:
                _merge(c, best)
            continue

        # generic recursion (shard_map, pjit, remat2, custom_*_call, ...)
        for sub in _sub_jaxprs(p):
            count_costs(sub, axis_sizes, c, mult)

    return c


def _merge(dst: Costs, src: Costs) -> None:
    dst.flops += src.flops
    dst.dot_bytes += src.dot_bytes
    for k, v in src.coll_bytes.items():
        dst.coll_bytes[k] = dst.coll_bytes.get(k, 0.0) + v
    dst.ppermute_fwd_bytes += src.ppermute_fwd_bytes
    dst.ppermute_bwd_bytes += src.ppermute_bwd_bytes
    dst.ppermute_fwd_steps += src.ppermute_fwd_steps
    dst.ppermute_bwd_steps += src.ppermute_bwd_steps
    dst.unknown_while += src.unknown_while
