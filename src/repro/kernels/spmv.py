"""Packed ELL SpMV kernel (the spmv / prank / sssp indirect benchmarks).

CSR on a vector machine iterates rows and gathers ``x[cols]`` — the paper's
flagship indirect stream.  The TPU-native layout is padded ELL: ``vals`` and
``cols`` are dense (rows × K) tiles streamed contiguously (packed by
construction), and the irregular part — gathering ``x`` by column index —
runs on-chip against an x panel resident in VMEM.  The element:index ratio
cost of the paper (§III-E) shows up here exactly: each nonzero moves one
``vals`` element *and* one ``cols`` index, so with 32-bit values and 32-bit
indices the useful-data fraction of the stream is r/(r+1) = 50 %.

Two variants:

* ``spmv_ell_kernel``       — x fully VMEM-resident (paper-scale matrices).
* ``spmv_ell_panel_kernel`` — x streamed in column panels for large n; cols
  must be panel-sorted (BCSR-style), the panel id per (row-block, step) is
  scalar-prefetched — an indirect stream descriptor driving the x DMAs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _spmv_body(vals_ref, cols_ref, x_ref, y_ref):
    x = x_ref[...].reshape(-1)
    cols = cols_ref[...]
    xg = jnp.take(x, cols, axis=0, mode="clip")  # in-VMEM indirect gather
    y_ref[...] = jnp.sum(vals_ref[...] * xg, axis=1, keepdims=True)


def spmv_ell_kernel(
    vals: jax.Array,
    cols: jax.Array,
    x: jax.Array,
    row_block: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """y = A @ x with A in padded-ELL form; x resident in VMEM.

    vals/cols: (R, K); x: (C,); returns y: (R,).
    """
    r, k = vals.shape
    (c,) = x.shape
    assert r % row_block == 0
    x2 = x.reshape(1, c)
    y = pl.pallas_call(
        _spmv_body,
        grid=(r // row_block,),
        in_specs=[
            pl.BlockSpec((row_block, k), lambda i: (i, 0)),
            pl.BlockSpec((row_block, k), lambda i: (i, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((row_block, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1), vals.dtype),
        name="spmv_ell",
        interpret=interpret,
    )(vals, cols, x2)
    return y.reshape(r)


def _spmv_panel_body(panel_ref, vals_ref, cols_ref, x_ref, y_ref, *, panel: int):
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    base = panel_ref[pl.program_id(0), s] * panel
    x = x_ref[...].reshape(-1)
    local = cols_ref[...] - base  # panel-local column offsets
    valid = (local >= 0) & (local < panel)
    xg = jnp.take(x, jnp.clip(local, 0, panel - 1), axis=0, mode="clip")
    contrib = jnp.where(valid, vals_ref[...] * xg, 0.0)
    y_ref[...] += jnp.sum(contrib, axis=1, keepdims=True)


def spmv_ell_panel_kernel(
    vals: jax.Array,
    cols: jax.Array,
    x: jax.Array,
    panel_ids: jax.Array,
    panel: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Panel-streamed SpMV: x arrives in VMEM one ``panel`` at a time.

    ``panel_ids`` (row_blocks, steps) int32 — which x panel each step of each
    row block needs (scalar-prefetched; the indirect stream descriptor).
    ``cols`` must be sorted so that step s of row block rb only references
    columns inside panel ``panel_ids[rb, s]`` — entries outside are masked.
    """
    r, k = vals.shape
    (c,) = x.shape
    row_blocks, steps = panel_ids.shape
    row_block = r // row_blocks
    assert k % steps == 0
    kb = k // steps
    x2 = x.reshape(c // panel, panel)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(row_blocks, steps),
        in_specs=[
            pl.BlockSpec((row_block, kb), lambda rb, s, p: (rb, s)),
            pl.BlockSpec((row_block, kb), lambda rb, s, p: (rb, s)),
            pl.BlockSpec((1, panel), lambda rb, s, p: (p[rb, s], 0)),
        ],
        out_specs=pl.BlockSpec((row_block, 1), lambda rb, s, p: (rb, 0)),
    )
    y = pl.pallas_call(
        functools.partial(_spmv_panel_body, panel=panel),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, 1), vals.dtype),
        name="spmv_ell_panel",
        interpret=interpret,
    )(panel_ids, vals, cols, x2)
    return y.reshape(r)
