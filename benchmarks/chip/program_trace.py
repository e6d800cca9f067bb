"""Per-layer readings from the program's own instrumentation: the device
time of the ops under a ``jax.named_scope``, the device idle time inside
a host span the program opens, and the window's readings of a telemetry
hub (``repro.core.telemetry``).

The op names of a profiler trace are the compiled program's instruction
names (``fusion.129``); :func:`op_scopes` maps them to their scope paths
from the program's optimized HLO text (``compiled.as_text()``).  XLA
inserts copies that carry no ``op_name``; such a copy takes the scope of
the value it copies: that of the value's producer, followed through
tuples and loop carries, or, where the value is a parameter of the
program passed into a loop, that of the op that writes its loop-carry
slot.

All times are in seconds, on the trace's common clock.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Optional

from benchmarks.chip.trace_reduce import (_line_events, device_planes, gaps,
                                          host_spans, leaves, short, union)

_HEADER = re.compile(r"^(?:ENTRY )?%([\w.\-]+) ")
_INSTR = re.compile(r"^\s+(ROOT )?%([\w.\-]+) = ")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_NAME = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INDEX = re.compile(r"\bindex=(\d+)")
_BODY = re.compile(r"\bbody=%([\w.\-]+)")
_PASS = ("copy", "bitcast")         # ops that hand their operand on


class _Op:
    __slots__ = ("opcode", "operands", "op_name", "index", "body")

    def __init__(self, line: str, start: int):
        m = _OPCODE.search(line, start)
        self.opcode = m.group(1)
        depth, i = 1, m.end()
        while depth:                    # the operand list's closing paren
            depth += {"(": 1, ")": -1}.get(line[i], 0)
            i += 1
        self.operands = _NAME.findall(line[m.end():i])
        rest = line[i:]
        name = _OP_NAME.search(rest)
        self.op_name = (name.group(1) if name and self.opcode != "parameter"
                        else None)
        index = _INDEX.search(rest)
        self.index = int(index.group(1)) if index else None
        body = _BODY.search(rest)
        self.body = body.group(1) if body else None


def _parse(hlo: str):
    ops: Dict[str, _Op] = {}
    roots: Dict[str, str] = {}          # computation -> its ROOT
    params: Dict[str, List[str]] = defaultdict(list)
    comp = None
    for line in hlo.splitlines():
        head = _HEADER.match(line)
        if head and line.rstrip().endswith("{"):
            comp = head.group(1)
            continue
        m = _INSTR.match(line)
        if m is None or comp is None:
            continue
        op = ops[m.group(2)] = _Op(line, m.end() - 1)
        if m.group(1):
            roots[comp] = m.group(2)
        if op.opcode == "parameter":
            params[comp].append(m.group(2))
    return ops, roots, params


def op_scopes(hlo: str) -> Dict[str, str]:
    """Each instruction's scope path (its ``op_name``) by its name, with
    XLA-inserted copies placed by the value they copy."""
    ops, roots, params = _parse(hlo)
    users: Dict[str, List[str]] = defaultdict(list)
    for name, op in ops.items():
        for x in op.operands:
            users[x].append(name)

    def produced(name: str, idx: Optional[int]) -> Optional[str]:
        op = ops.get(name)
        if op is None or op.opcode == "parameter":
            return None
        if op.opcode == "get-tuple-element":
            return produced(op.operands[0], op.index)
        if op.opcode == "tuple":
            return None if idx is None else produced(op.operands[idx], None)
        if op.opcode == "while":
            return produced(roots[op.body], idx)
        if op.opcode in _PASS and op.op_name is None:
            return produced(op.operands[0], idx)
        return op.op_name

    def carried(name: str) -> Optional[str]:
        """The scope of the loop-carry slot the value is passed into."""
        for t in users[name]:
            if ops[t].opcode == "tuple":
                idx = ops[t].operands.index(name)
                for w in users[t]:
                    if ops[w].opcode == "while":
                        return produced(roots[ops[w].body], idx)
        return None

    scopes = {}
    for name, op in ops.items():
        scope = op.op_name
        if scope is None and op.opcode == "copy":
            scope = produced(op.operands[0], None) or carried(name)
        if scope:
            scopes[name] = scope
    return scopes


def in_scope(scope: Optional[str], name: str) -> bool:
    return scope is not None and name in scope.split("/")


def program_layers(pd, scopes: Dict[str, str], *, scope: str,
                   gap_span: str, stretch_span: str,
                   module_match: str) -> Dict:
    """Over the stretch from the first to the last host span named
    ``stretch_span``, averaged over the device planes, per step program
    (a module whose name contains ``module_match``): the device time of
    the leaf ops under ``scope`` that run inside a step (``scoped_s``;
    each such op's time in ``scoped_ops``), and the device idle time of
    the gaps whose midpoint lies in a host span named ``gap_span``
    (``gap_idle_s``).  Empty where the trace has no stretch, no step or
    no op under the scope."""
    marks = [s for s in host_spans(pd, stretch_span) if s[0] == stretch_span]
    planes = device_planes(pd)
    if not marks or not planes:
        return {}
    t0, t1 = marks[0][1], marks[-1][2]
    inside = [(a, b) for name, a, b in host_spans(pd, gap_span)
              if name == gap_span]
    steps, scoped, idle = 0, defaultdict(float), 0.0
    for plane in planes:
        mods = sorted((a, b) for name, a, b in _line_events(
            plane, "XLA Modules")
            if module_match in name and a >= t0 and b <= t1)
        steps += len(mods)
        starts = [a for a, _ in mods]
        ops = _line_events(plane, "XLA Ops")
        for name, a, b in leaves(ops):
            i = bisect.bisect_right(starts, a) - 1      # the step it runs in
            op = short(name)
            if i >= 0 and b <= mods[i][1] and in_scope(scopes.get(op),
                                                       scope):
                scoped[op] += b - a
        for a, b in gaps(union([(a, b) for _, a, b in ops]), t0, t1):
            mid = 0.5 * (a + b)
            if any(x <= mid <= y for x, y in inside):
                idle += b - a
    if not steps or not scoped:
        return {}
    return {"steps": steps / len(planes),
            "scoped_s": sum(scoped.values()) / steps,
            "scoped_ops": sorted(([k, v / steps] for k, v in scoped.items()),
                                 key=lambda kv: -kv[1]),
            "gap_idle_s": idle / steps}


def hub_window(before: Dict, after: Dict) -> Dict:
    """From two snapshots of a telemetry hub, at the window's start and
    end: the scheduler's own time per round (``sched.step`` less
    ``sched.decode``) and the mean time of a result on the wire
    (``serve.result_wire``), in seconds.  A reading the hub did not
    record is left out."""
    def diff(kind, name, key):
        return (after.get(kind, {}).get(name, {}).get(key, 0)
                - before.get(kind, {}).get(name, {}).get(key, 0))

    out = {}
    rounds = diff("spans", "sched.step", "count")
    if rounds:
        out["sched_self_s_per_round"] = 1e-9 * (
            diff("spans", "sched.step", "sum")
            - diff("spans", "sched.decode", "sum")) / rounds
    wire = diff("hists", "serve.result_wire", "count")
    if wire:
        out["result_wire_s"] = 1e-9 * diff("hists", "serve.result_wire",
                                           "sum") / wire
    return out

