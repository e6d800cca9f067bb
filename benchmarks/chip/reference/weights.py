"""Seeded weights in the layout the program takes, made by the benchmark.

``shapes`` is a nested dict whose leaves are ``(shape, dtype)``; ``init``
maps (leaf name, shape, key) to an f32 array.  The whole tree is made on
the device in one jitted call and cast to each leaf's stored dtype.
"""
from __future__ import annotations

import jax


def _leaves(tree, prefix=""):
    for name in sorted(tree):
        sub = tree[name]
        if isinstance(sub, dict):
            yield from _leaves(sub, prefix + name + "/")
        else:
            yield prefix + name, sub


def make(shapes: dict, init, seed: int) -> dict:
    leaves = list(_leaves(shapes))

    def build(key):
        out = {}
        for i, (name, (shape, dtype)) in enumerate(leaves):
            w = init(name.rsplit("/", 1)[-1], shape,
                     jax.random.fold_in(key, i)).astype(dtype)
            node = out
            *path, last = name.split("/")
            for p in path:
                node = node.setdefault(p, {})
            node[last] = w
        return out

    return jax.jit(build)(jax.random.key(seed % 2**32))


def check_layout(ours: dict, program: dict) -> None:
    """Fail unless the program's abstract params have our tree exactly."""
    a = {n: (tuple(s), str(jax.numpy.dtype(d))) for n, (s, d) in
         _leaves(ours)}
    b = {n: (tuple(x.shape), str(x.dtype)) for n, x in _leaves(program)}
    if a != b:
        diff = sorted(set(a.items()) ^ set(b.items()))
        raise ValueError(f"program parameter layout differs from the "
                         f"benchmark's: {diff[:6]}")
