"""Host spans the benchmark puts around each call into a layer.

Each span is kept in memory as (name, start, end) on the host clock, and,
when the run is traced, also written into the profiler's trace as a
``jax.profiler.TraceAnnotation`` so that idle gaps on the device can be
attributed to what the host was doing.
"""
from __future__ import annotations

import contextlib
import time
from typing import List, Tuple

import jax


class Spans:
    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.rows: List[Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        try:
            if self.annotate:
                with jax.profiler.TraceAnnotation(name):
                    yield
            else:
                yield
        finally:
            self.rows.append((name, t0, time.perf_counter()))

    def total(self, name: str, t0: float = float("-inf"),
              t1: float = float("inf")) -> Tuple[float, int]:
        """Summed seconds and count of the spans ``name`` inside [t0, t1]."""
        sel = [b - a for n, a, b in self.rows
               if n == name and a >= t0 and b <= t1]
        return sum(sel), len(sel)
