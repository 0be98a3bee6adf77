"""Pure-JAX packing engine: functional semantics of packed irregular streams.

These are the *reference semantics* of AXI-Pack bursts — what the data looks
like after the beat packer has run.  The Pallas kernels in
:mod:`repro.kernels` implement the same functions with explicit HBM→VMEM
streaming; everything here is differentiable, jit-able jnp and serves as the
oracle (``ref``) implementation plus the paper's traffic accounting (bytes
moved under BASE vs PACK semantics).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "pack_strided",
    "unpack_strided",
    "pack_indirect",
    "unpack_indirect",
    "Traffic",
    "strided_traffic",
    "indirect_traffic",
]


def pack_strided(src: jax.Array, base: int, stride: int, count: int) -> jax.Array:
    """Gather ``count`` rows of ``src`` at ``base + k*stride`` into a dense block.

    ``src`` has shape (n_rows, *row); the result has shape (count, *row).
    With stride == 1 this is a contiguous slice (the base converter path).
    """
    if stride == 1:
        return jax.lax.dynamic_slice_in_dim(src, base, count, axis=0)
    idx = base + stride * jnp.arange(count)
    return jnp.take(src, idx, axis=0)


def unpack_strided(
    dst: jax.Array, packed: jax.Array, base: int, stride: int
) -> jax.Array:
    """Scatter the rows of ``packed`` back to ``dst`` at ``base + k*stride``."""
    count = packed.shape[0]
    if stride == 1:
        return jax.lax.dynamic_update_slice_in_dim(dst, packed, base, axis=0)
    idx = base + stride * jnp.arange(count)
    return dst.at[idx].set(packed)


def pack_indirect(src: jax.Array, indices: jax.Array, base: int = 0) -> jax.Array:
    """Gather rows ``src[base + indices[k]]`` into a dense block.

    The index array is a *memory-resident* JAX array (the in-memory indexed
    semantics of ``vlimxei``): callers never materialize per-element addresses.
    """
    return jnp.take(src, base + indices, axis=0)


def unpack_indirect(
    dst: jax.Array,
    packed: jax.Array,
    indices: jax.Array,
    base: int = 0,
    mode: str = "set",
) -> jax.Array:
    """Scatter rows of ``packed`` to ``dst[base + indices[k]]``.

    ``mode='set'`` mirrors the hardware write converter (last-writer-wins for
    duplicate indices, order unspecified); ``mode='add'`` accumulates, which
    the framework uses for MoE combine and embedding gradients.
    """
    at = dst.at[base + indices]
    return at.add(packed) if mode == "add" else at.set(packed)


# ---------------------------------------------------------------------------
# Traffic accounting: exact bytes moved under each system's semantics.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Traffic:
    """HBM/bus traffic for one logical transfer, per system.

    ``base_bytes`` counts one full bus/transaction granule per element (the
    narrow-beat penalty); ``pack_bytes`` counts densely packed lines;
    ``index_bus_bytes`` is index traffic crossing the core-side bus (zero for
    PACK, whose indirection is endpoint-side).
    """

    useful_bytes: int
    base_bytes: int
    pack_bytes: int
    index_bus_bytes_base: int
    index_bus_bytes_pack: int = 0

    @property
    def base_efficiency(self) -> float:
        tot = self.base_bytes + self.index_bus_bytes_base
        return self.useful_bytes / tot if tot else 1.0

    @property
    def pack_efficiency(self) -> float:
        tot = self.pack_bytes + self.index_bus_bytes_pack
        return self.useful_bytes / tot if tot else 1.0


def strided_traffic(
    count: int, elem_bytes: int, stride: int, granule_bytes: int = 32
) -> Traffic:
    """Traffic for a strided stream on a ``granule_bytes``-wide bus."""
    useful = count * elem_bytes
    if stride == 1:
        moved = int(np.ceil(useful / granule_bytes)) * granule_bytes
        return Traffic(useful, moved, moved, 0)
    base = count * granule_bytes                      # one narrow beat/elem
    pack = int(np.ceil(useful / granule_bytes)) * granule_bytes
    return Traffic(useful, base, pack, 0)


def indirect_traffic(
    count: int, elem_bytes: int, index_bytes: int, granule_bytes: int = 32
) -> Traffic:
    """Traffic for an indirect stream; indices are packed lines either way."""
    useful = count * elem_bytes
    idx = int(np.ceil(count * index_bytes / granule_bytes)) * granule_bytes
    base = count * granule_bytes
    pack = int(np.ceil(useful / granule_bytes)) * granule_bytes
    # PACK fetches indices endpoint-side: they cost memory bandwidth but not
    # core-side bus bytes; we still report them for the HBM energy proxy.
    return Traffic(useful, base, pack, idx, 0)
