"""Multi-query paged verify attention — K draft tokens per page walk.

Speculative decoding's verify step scores the feed token plus K-1 draft
tokens for every sequence in **one** clamped, scalar-prefetched walk over
that sequence's context pages.  Structurally this is the flash-prefill
kernel (:mod:`repro.kernels.paged_prefill`) with a tiny causal chunk:
row ``r``'s query ``i`` sits at absolute position ``lengths[r] + i`` and
attends everything written up to and including itself, so the chunk body
— clamped index map, online softmax, in-VMEM GQA grouping and int8
dequant — is *identical* to prefill with ``starts = lengths``.  We reuse
``_prefill_body`` directly rather than fork it: the verify kernel is the
prefill kernel at chunk size K, and keeping one body keeps the two paths
bit-identical by construction.

What makes this the speculative *perf* kernel is the amortization: plain
decode walks every context page once per generated token (K narrow
indirect bursts for K tokens), while verify walks them once per K-token
batch — the AXI-Pack packed-indirect-burst argument applied along the
time axis instead of the batch axis.

The grid is ``(B, ctx_pages)`` with the per-row walk clamped to
``ceil((lengths[r] + counts[r]) / page)`` pages; rows with
``counts[r] == 0`` (inactive slots, capacity-clamped slots) are padding
rows — their walk clamps to the row's first table entry and they output
exact zeros, never NaNs.  The K query tokens' own K/V rows must already
be appended to the pool (the engine writes the chunk first, exactly as
prefill does).
"""
from __future__ import annotations

from typing import Optional

import jax

from .paged_prefill import paged_prefill_attention_kernel


def paged_verify_attention_kernel(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    ctx_rows: jax.Array,
    lengths: jax.Array,
    counts: jax.Array,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Score K speculative query tokens per sequence in one page walk.

    q:          (B, K, H, D) verify queries — query ``i`` of row ``r`` is
                the token at absolute position ``lengths[r] + i`` (the feed
                token at i=0, drafts after it)
    k/v_pages:  (P, page, KVH, D) physical pool; the K query tokens' K/V
                must already be written (append precedes attention, as in
                prefill); int8 codes when scales are given
    ctx_rows:   (B, ctx_pages) leading page-table entries per row
    lengths:    (B,) tokens already in each row's context *before* this
                verify chunk
    counts:     (B,) valid query tokens per row (0..K; 0 = padding row,
                zero output)
    k/v_scale:  optional (P, page, KVH) fp32 scale pools riding the same
                clamped index map (int8 pools)

    Returns (B, K, H, D) attention outputs.  Bit-identical to
    ``paged_prefill_attention_kernel(q, ..., starts=lengths, counts)`` —
    a verify chunk *is* a causal prefill chunk appended at the context
    tail.
    """
    return paged_prefill_attention_kernel(
        q, k_pages, v_pages, ctx_rows, lengths, counts,
        k_scale=k_scale, v_scale=v_scale, scale=scale, interpret=interpret,
    )
