"""Public wrapper for the latent decode kernel: the compiled kernel on
the TPU, the same kernel in the Pallas interpreter on any other backend,
chosen when the program is lowered for its platform."""
from __future__ import annotations

import functools

import jax

from .kernel import block_rows, latent_decode_tpu


def latent_decode(qc, lat_all, layer, live, *, rank: int):
    """qc (b, nq, W) f32; lat_all (L, b, S, W) the whole latent cache ->
    the partial flash triple (num (b, nq, rank), m (b, nq), l (b, nq)) of
    layer ``layer``'s rows below ``live``, each live block read once."""
    run = functools.partial(latent_decode_tpu, rank=rank)
    return jax.lax.platform_dependent(
        qc, lat_all, layer, live, tpu=run,
        default=functools.partial(run, interpret=True))


def blocks_read(live, seq_len: int):
    """Row blocks :func:`latent_decode` reads for ``live`` rows of a
    ``seq_len``-row cache."""
    block = block_rows(seq_len)
    return (live + block - 1) // block
