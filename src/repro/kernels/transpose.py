"""Tiled matrix transpose kernel (the ismt strided-stream benchmark).

The paper's ``ismt`` swaps elements above/below the diagonal with strided
accesses.  The TPU-native formulation streams (bt × bt) tiles: the input
tile at (j, i) is a *strided tile stream* relative to the output walk at
(i, j) — each output tile's source is one stride-length away in the transposed
direction, and the tile itself is transposed on the VPU between two dense
DMAs.  BASE-equivalent behaviour (per-element narrow access) is what XLA's
generic gather would do; the packed version moves only full tiles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _transpose_body(x_ref, out_ref):
    out_ref[...] = jnp.swapaxes(x_ref[...], 0, 1)


def transpose_kernel(
    x: jax.Array, block: int = 128, interpret: bool = False
) -> jax.Array:
    """Transpose a 2-D array with (block × block) VMEM tiles."""
    r, c = x.shape
    assert r % block == 0 and c % block == 0, "wrapper must pad to block"
    return pl.pallas_call(
        _transpose_body,
        grid=(r // block, c // block),
        in_specs=[pl.BlockSpec((block, block), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((block, block), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((c, r), x.dtype),
        name="transpose",
        interpret=interpret,
    )(x)
