"""Packed irregular stream descriptors — the software form of AXI-Pack requests.

AXI-Pack encodes stream semantics into the AXI4 AR/AW request channels via
user-field bits: ``pack`` (extension active), ``indir`` (strided vs indirect),
and a shared field carrying either the element stride or the index base/size.
This module is the JAX-side equivalent: a :class:`StridedStream` or
:class:`IndirectStream` fully describes an irregular access sequence, and the
rest of the framework (packing engine, Pallas kernels, bus model, bank
simulator) consumes these descriptors instead of raw address lists.

Descriptors are deliberately *dataclasses of ints*, not arrays: like an AXI
request they are cheap metadata travelling ahead of the data.  The index array
of an :class:`IndirectStream` stays *in memory* (a JAX array reference) and is
resolved near-memory (scalar-prefetch in the Pallas kernels, index stage in
the bank simulator) — never round-tripped through the "core side".
"""
from __future__ import annotations

import dataclasses
import enum
import math
from typing import Optional

import numpy as np

__all__ = [
    "BurstKind",
    "StreamDescriptor",
    "ContiguousStream",
    "StridedStream",
    "IndirectStream",
    "elements_per_beat",
    "beats_for",
]


class BurstKind(enum.Enum):
    """The three burst families AXI-Pack distinguishes.

    ``BASE`` corresponds to an ordinary AXI4 contiguous burst (the ``base``
    converter in the paper's controller); ``STRIDED`` and ``INDIRECT`` are the
    two new packed burst types signalled by the ``pack``/``indir`` user bits.
    """

    BASE = "base"
    STRIDED = "strided"
    INDIRECT = "indirect"


def elements_per_beat(bus_bits: int, elem_bits: int) -> int:
    """How many elements a single packed bus beat carries (n = D/W in §II-C)."""
    if elem_bits > bus_bits:
        raise ValueError(f"element ({elem_bits}b) wider than bus ({bus_bits}b)")
    return bus_bits // elem_bits


def beats_for(n_elems: int, bus_bits: int, elem_bits: int) -> int:
    """Beats needed to carry ``n_elems`` densely packed elements."""
    if n_elems == 0:
        return 0
    return math.ceil(n_elems * elem_bits / bus_bits)


@dataclasses.dataclass(frozen=True)
class StreamDescriptor:
    """Base class for stream descriptors.

    Attributes:
      base: element offset of the first element in the source array (in
        elements, mirroring the paper's bus-aligned semantics).
      elem_bits: element width in bits (AR/AW ``size`` field under AXI-Pack).
      count: number of elements in the stream (burst length × packing factor).
    """

    base: int
    elem_bits: int
    count: int

    kind: BurstKind = dataclasses.field(default=BurstKind.BASE, init=False)

    def element_offsets(self) -> np.ndarray:
        """Absolute element offsets touched by the stream, in stream order."""
        raise NotImplementedError

    @property
    def bytes(self) -> int:
        return self.count * self.elem_bits // 8


@dataclasses.dataclass(frozen=True)
class ContiguousStream(StreamDescriptor):
    """A plain AXI4 burst: ``count`` elements starting at ``base``."""

    def __post_init__(self):
        object.__setattr__(self, "kind", BurstKind.BASE)

    def element_offsets(self) -> np.ndarray:
        return self.base + np.arange(self.count, dtype=np.int64)


@dataclasses.dataclass(frozen=True)
class StridedStream(StreamDescriptor):
    """A packed strided burst: elements at ``base + k*stride``.

    ``stride`` is in elements, like the user-field stride of AXI-Pack. A
    stride of 1 degenerates to a contiguous burst and is routed to the base
    converter (the paper's never-slower-than-AXI4 guarantee).
    """

    stride: int = 1

    def __post_init__(self):
        object.__setattr__(
            self, "kind", BurstKind.BASE if self.stride == 1 else BurstKind.STRIDED
        )

    def element_offsets(self) -> np.ndarray:
        return self.base + self.stride * np.arange(self.count, dtype=np.int64)


@dataclasses.dataclass(frozen=True)
class IndirectStream(StreamDescriptor):
    """A packed indirect burst: elements at ``base + index[k]``.

    The index array lives in memory (``indices``), matching the new
    ``vlimxei``/``vsimxei`` in-memory indexed instructions: indirection is
    resolved at the endpoint, so indices never consume core-side bandwidth.

    Attributes:
      indices: int array of ``count`` element offsets (relative to ``base``).
      index_bits: index element width (8/16/32), which sets the element:index
        ratio r and the r/(r+1) utilization ceiling of §III-E.
      remap_only: the stream repoints table entries without moving element
        payload (prefix sharing): only the index fetch touches memory.  The
        element fields still describe the pages being reused, so accounting
        can value the remap, but simulators must drain just the index lines.
    """

    indices: Optional[np.ndarray] = None
    index_bits: int = 32
    remap_only: bool = False

    def __post_init__(self):
        object.__setattr__(self, "kind", BurstKind.INDIRECT)
        if self.indices is None:
            raise ValueError("IndirectStream requires an index array")
        idx = np.asarray(self.indices)
        if idx.ndim != 1 or idx.shape[0] != self.count:
            raise ValueError(
                f"index array shape {idx.shape} does not match count={self.count}"
            )

    @property
    def ratio(self) -> float:
        """Element:index size ratio r — sets the r/(r+1) packing ceiling."""
        return self.elem_bits / self.index_bits

    @property
    def index_bytes(self) -> int:
        return self.count * self.index_bits // 8

    def element_offsets(self) -> np.ndarray:
        return self.base + np.asarray(self.indices, dtype=np.int64)


def word_addresses(
    stream: StreamDescriptor, word_bits: int = 32
) -> np.ndarray:
    """Map a stream's element offsets to memory *word* addresses.

    The banked controller operates on W-bit words (the bank width); an element
    smaller than a word still occupies one word access, while an element
    spanning multiple words issues several.  Returns the flat sequence of word
    addresses in stream order (used by the bank-conflict simulator).
    """
    offs = stream.element_offsets()
    if stream.elem_bits <= word_bits:
        scale = word_bits // stream.elem_bits
        return offs // scale
    words_per_elem = stream.elem_bits // word_bits
    base_words = offs * words_per_elem
    return (base_words[:, None] + np.arange(words_per_elem)[None, :]).reshape(-1)
