"""Pallas TPU kernels for the four packed irregular-stream converters.

These are the TPU-native forms of the paper's controller datapaths (Fig. 2):

* ``strided_gather_kernel``  — strided read converter: rows at
  ``base + k*stride`` are fetched by per-row DMAs whose addresses come from a
  *static* stride (no index traffic at all, like the stride field of an
  AXI-Pack AR request) and land densely packed in the output.
* ``strided_scatter_kernel`` — strided write converter (beat unpacker).
* ``indirect_gather_kernel`` — indirect read converter: the index array is
  **scalar-prefetched into SMEM** and each entry addresses one row DMA, so
  the DMA engine itself resolves the indirection near memory — the Pallas
  equivalent of the paper's index stage feeding the element request
  generator.  The compute core never touches the rows.
* ``indirect_scatter_kernel`` — indirect write converter (aliased output so
  untouched destination rows are preserved; duplicate indices are
  last-writer-wins in stream order; out-of-range indices write nothing).

Every kernel is a row-DMA engine over arrays left in HBM (``pl.ANY``): each
grid step issues up to ``PACK_ROWS`` HBM→HBM row copies on one DMA semaphore
and then waits for all of them, so a step is one packed burst of rows.  A row
is ``x[i]`` of an ``(n, *row)`` array.  Mosaic can slice HBM only along
leading dimensions, and only when the row's two minor dimensions fit the
dtype's tiling: last dim a multiple of 128, and for 16- and 8-bit data a
second-minor dim that is a power of two from the sublane packing (2 or 4) up
to 8, or a multiple of 8 (:func:`_sublanes_ok`).  Rows that already have
that shape — the ``(KVH, 128)`` token rows and ``(page, KVH, 128)`` pages of
a K/V pool, the flattened state rows of a recurrent pool — are DMA'd in
place; any other row is flattened, zero-padded to the next allowed
``(s, 128)`` shape and viewed as such (a copy of the array).

Hardware-adaptation note: AXI-Pack packs at *word* (32-bit) granularity
because its banked endpoint has 32-bit banks.  HBM has no word-granular
access — the efficient granule is a ~512 B transaction — so the TPU-native
stream granule is a **row** (≥128 lanes).  Element-granular strided access is
provided by the models/benchmarks at tile level (e.g. ismt works on (8,128)
tiles); see DESIGN.md §2.
"""
from __future__ import annotations

import functools
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PACK_ROWS = 8  # row DMAs in flight per grid step (one packed burst)

_HBM = pl.BlockSpec(memory_space=pl.ANY)


# ---------------------------------------------------------------------------
# Row views and the shared row-DMA body
# ---------------------------------------------------------------------------


def _sublane_packing(dtype) -> int:
    return max(1, 4 // jnp.dtype(dtype).itemsize)


def _sublanes_ok(s: int, packing: int) -> bool:
    """Whether Mosaic slices an ``(s, 128·k)`` row out of HBM (v5e, measured
    by compiling): any ``s`` for 32-bit data; for packed dtypes a power of
    two from ``packing`` up to 8, or any multiple of 8."""
    return packing == 1 or s % 8 == 0 or (s >= packing and s & (s - 1) == 0)


def _dma_view(x: jax.Array) -> Tuple[jax.Array, Callable[[jax.Array], jax.Array]]:
    """``x`` (n, *row) → (a view whose rows Mosaic can DMA, the inverse)."""
    n, row = x.shape[0], x.shape[1:]
    packing = _sublane_packing(x.dtype)
    if len(row) >= 2 and row[-1] % 128 == 0 and _sublanes_ok(row[-2], packing):
        return x, lambda y: y
    w = int(np.prod(row))
    s = -(-w // 128)
    while not _sublanes_ok(s, packing):
        s += 1
    padded = s * 128
    v = x.reshape(n, w)
    if padded != w:
        v = jnp.pad(v, ((0, 0), (0, padded - w)))

    def undo(y):
        return y.reshape(y.shape[0], padded)[:, :w].reshape((y.shape[0],) + row)

    return v.reshape(n, s, 128), undo


def _row_dmas(src_ref, dst_ref, sem, *, count: int, src_row, dst_row,
              valid=None):
    """Copy rows ``src_row(k) → dst_row(k)`` for this step's ``k``s.

    All copies of the step are started before the first wait, so up to
    ``PACK_ROWS`` row DMAs are in flight at once; ``valid(k)`` (optional)
    predicates a row off — its DMA is neither started nor awaited.
    ``valid`` and the row functions only ever see ``k < count``.
    """
    lo = pl.program_id(0) * PACK_ROWS

    def copy(k):
        return pltpu.make_async_copy(
            src_ref.at[src_row(k)], dst_ref.at[dst_row(k)], sem
        )

    def each(k, op):
        @pl.when(k < count)
        def _():
            if valid is None:
                op(copy(k))
            else:
                pl.when(valid(k))(lambda: op(copy(k)))

    for r in range(PACK_ROWS):
        each(lo + r, lambda c: c.start())
    for r in range(PACK_ROWS):
        each(lo + r, lambda c: c.wait())


def _grid(count: int) -> Tuple[int]:
    return (max(1, -(-count // PACK_ROWS)),)


# ---------------------------------------------------------------------------
# Strided read converter
# ---------------------------------------------------------------------------


def _strided_gather_body(src_ref, out_ref, sem, *, n, base, stride, count):
    _row_dmas(
        src_ref, out_ref, sem, count=count,
        # Reads past the end clamp to the last row (the oracle's mode='clip').
        src_row=lambda k: jnp.minimum(base + k * stride, n - 1),
        dst_row=lambda k: k,
    )


def strided_gather_kernel(
    src: jax.Array,
    base: int,
    stride: int,
    count: int,
    interpret: bool = False,
) -> jax.Array:
    """Gather ``count`` rows at ``base + k*stride`` into a packed (count, *row) block.

    ``base``/``stride`` are static, mirroring the AR user field of a strided
    AXI-Pack burst: the request fully describes the stream, no index memory
    traffic is issued.
    """
    if count == 0:
        return src[:0]
    view, undo = _dma_view(src)
    out = pl.pallas_call(
        functools.partial(
            _strided_gather_body, n=src.shape[0], base=base, stride=stride,
            count=count,
        ),
        grid=_grid(count),
        in_specs=[_HBM],
        out_specs=_HBM,
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        out_shape=jax.ShapeDtypeStruct((count,) + view.shape[1:], view.dtype),
        name="strided_gather",
        interpret=interpret,
    )(view)
    return undo(out)


# ---------------------------------------------------------------------------
# Strided write converter
# ---------------------------------------------------------------------------


def _strided_scatter_body(packed_ref, dst_ref, out_ref, sem, *, n, base,
                          stride, count):
    del dst_ref  # aliased to out_ref
    _row_dmas(
        packed_ref, out_ref, sem, count=count,
        src_row=lambda k: k,
        dst_row=lambda k: base + k * stride,
        valid=lambda k: base + k * stride < n,
    )


def strided_scatter_kernel(
    dst: jax.Array,
    packed: jax.Array,
    base: int,
    stride: int,
    interpret: bool = False,
) -> jax.Array:
    """Scatter packed rows to ``dst[base + k*stride]`` (beat unpacker).

    Rows whose target falls past the end of ``dst`` are dropped; rows are
    cast to ``dst``'s dtype, as an XLA scatter would.
    """
    count = packed.shape[0]
    if count == 0:
        return dst
    dst_v, undo = _dma_view(dst)
    packed_v, _ = _dma_view(packed.astype(dst.dtype))
    out = pl.pallas_call(
        functools.partial(
            _strided_scatter_body, n=dst.shape[0], base=base, stride=stride,
            count=count,
        ),
        grid=_grid(count),
        in_specs=[_HBM, _HBM],
        out_specs=_HBM,
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        out_shape=jax.ShapeDtypeStruct(dst_v.shape, dst_v.dtype),
        input_output_aliases={1: 0},
        name="strided_scatter",
        interpret=interpret,
    )(packed_v, dst_v)
    return undo(out)


# ---------------------------------------------------------------------------
# Indirect read converter (scalar-prefetched index stage)
# ---------------------------------------------------------------------------


def _indirect_gather_body(idx_ref, src_ref, out_ref, sem, *, n, count):
    _row_dmas(
        src_ref, out_ref, sem, count=count,
        # Out-of-range indices clamp (the oracle's mode='clip').
        src_row=lambda k: jnp.clip(idx_ref[k], 0, n - 1),
        dst_row=lambda k: k,
    )


def indirect_gather_kernel(
    src: jax.Array,
    indices: jax.Array,
    interpret: bool = False,
) -> jax.Array:
    """Gather rows ``src[indices[k]]`` into a packed block.

    The index array rides the scalar-prefetch channel (SMEM) and addresses
    the row DMAs directly — the element DMAs are issued from the indices
    without the data ever detouring through the core, the exact analogue of
    memory-side indirection (``vlimxei``).
    """
    (count,) = indices.shape
    if count == 0:
        return src[:0]
    view, undo = _dma_view(src)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=_grid(count),
        in_specs=[_HBM],
        out_specs=_HBM,
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    out = pl.pallas_call(
        functools.partial(_indirect_gather_body, n=src.shape[0], count=count),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((count,) + view.shape[1:], view.dtype),
        name="indirect_gather",
        interpret=interpret,
    )(indices.astype(jnp.int32), view)
    return undo(out)


# ---------------------------------------------------------------------------
# Indirect write converter
# ---------------------------------------------------------------------------


def _last_writer(indices: jax.Array, n: int) -> jax.Array:
    """Route every write that a later duplicate overrides to index ``n``
    (dropped), so the row DMAs in flight together never share a target."""
    order = jnp.argsort(indices, stable=True)
    s = indices[order]
    last = jnp.concatenate([s[1:] != s[:-1], jnp.ones((1,), bool)])
    keep = jnp.zeros_like(last).at[order].set(last)
    return jnp.where(keep, indices, n)


def _indirect_scatter_body(idx_ref, packed_ref, dst_ref, out_ref, sem, *, n,
                           count):
    del dst_ref  # aliased to out_ref
    _row_dmas(
        packed_ref, out_ref, sem, count=count,
        src_row=lambda k: k,
        dst_row=lambda k: idx_ref[k],
        valid=lambda k: (idx_ref[k] >= 0) & (idx_ref[k] < n),
    )


def indirect_scatter_kernel(
    dst: jax.Array,
    packed: jax.Array,
    indices: jax.Array,
    interpret: bool = False,
) -> jax.Array:
    """Scatter packed rows to ``dst[indices[k]]``; untouched rows preserved.

    Duplicate indices resolve last-writer-wins in stream order (the earlier
    writes are dropped before launch, so concurrent row DMAs never race),
    and indices outside ``[0, n)`` write nothing — callers route rows they
    want discarded out of range.  Rows are cast to ``dst``'s dtype, as an
    XLA scatter would.  Callers needing accumulation use the ``ref`` add
    path or MoE combine.
    """
    (count,) = indices.shape
    if count == 0:
        return dst
    n = dst.shape[0]
    dst_v, undo = _dma_view(dst)
    packed_v, _ = _dma_view(packed.astype(dst.dtype))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=_grid(count),
        in_specs=[_HBM, _HBM],
        out_specs=_HBM,
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    out = pl.pallas_call(
        functools.partial(_indirect_scatter_body, n=n, count=count),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(dst_v.shape, dst_v.dtype),
        input_output_aliases={2: 0},
        name="indirect_scatter",
        interpret=interpret,
    )(_last_writer(indices.astype(jnp.int32), n), packed_v, dst_v)
    return undo(out)
