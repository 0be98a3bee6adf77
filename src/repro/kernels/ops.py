"""Public jit'd wrappers around the Pallas kernels.

Every op takes ``impl`` ∈ {'pallas', 'ref'}:

* ``'pallas'`` — the TPU kernel (``interpret=True`` automatically on a CPU
  host, so the same call validates in the tests; compiled by Mosaic on a
  TPU);
* ``'ref'``    — the pure-jnp oracle (differentiable; used for training paths
  that need gradients and as the allclose ground truth).

Wrappers own the shape work around the kernels: padding workload tiles and
reshaping pools into the row stacks the stream converters move (the
converters align rows to the chip's tiling themselves).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref
from .stream_converters import (
    indirect_gather_kernel,
    indirect_scatter_kernel,
    strided_gather_kernel,
    strided_scatter_kernel,
)
from .transpose import transpose_kernel
from .spmv import spmv_ell_kernel
from .flash_attention import flash_attention_kernel
from .paged_decode import paged_decode_attention_kernel
from .paged_prefill import paged_prefill_attention_kernel
from .paged_verify import paged_verify_attention_kernel

__all__ = [
    "on_cpu",
    "strided_gather",
    "strided_scatter",
    "indirect_gather",
    "indirect_scatter",
    "tiled_transpose",
    "spmv_ell",
    "flash_attention",
    "paged_decode_attention",
    "paged_prefill_attention",
    "paged_verify",
    "speculative_accept",
    "paged_kv_append",
    "paged_kv_write_chunk",
    "moe_dispatch",
    "moe_combine",
]


def on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _interpret() -> bool:
    # Pallas TPU kernels run through the interpreter on CPU hosts.
    return on_cpu()


def _pad_rows(x: jax.Array, mult: int) -> Tuple[jax.Array, int]:
    n = x.shape[0]
    pad = (-n) % mult
    if pad:
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    return x, n


# ---------------------------------------------------------------------------
# Stream converters
# ---------------------------------------------------------------------------


def strided_gather(
    src: jax.Array, base: int, stride: int, count: int, impl: str = "pallas"
) -> jax.Array:
    """out[k] = src[base + k*stride] — packed strided read."""
    if impl == "ref" or stride == 1:
        # stride==1 → the base (contiguous) converter: plain dynamic slice.
        return ref.strided_gather(src, base, stride, count)
    return strided_gather_kernel(src, base, stride, count, interpret=_interpret())


def strided_scatter(
    dst: jax.Array, packed: jax.Array, base: int, stride: int, impl: str = "pallas"
) -> jax.Array:
    """dst[base + k*stride] = packed[k] — packed strided write."""
    if impl == "ref" or stride == 1:
        return ref.strided_scatter(dst, packed, base, stride)
    return strided_scatter_kernel(dst, packed, base, stride, interpret=_interpret())


def indirect_gather(
    src: jax.Array, indices: jax.Array, impl: str = "pallas"
) -> jax.Array:
    """out[k] = src[indices[k]] — packed indirect read (in-memory indices)."""
    if impl == "ref":
        return ref.indirect_gather(src, indices)
    return indirect_gather_kernel(src, indices, interpret=_interpret())


def indirect_scatter(
    dst: jax.Array,
    packed: jax.Array,
    indices: jax.Array,
    mode: str = "set",
    impl: str = "pallas",
) -> jax.Array:
    """dst[indices[k]] = packed[k] — packed indirect write.

    Out-of-range indices write nothing; duplicates are last-writer-wins.
    """
    if impl == "ref" or mode == "add":
        # Accumulating scatter needs read-modify-write; route to ref.
        return ref.indirect_scatter(dst, packed, indices, mode)
    return indirect_scatter_kernel(dst, packed, indices, interpret=_interpret())


# ---------------------------------------------------------------------------
# Workload kernels
# ---------------------------------------------------------------------------


def tiled_transpose(x: jax.Array, block: int = 128, impl: str = "pallas") -> jax.Array:
    if impl == "ref":
        return ref.tiled_transpose(x)
    r, c = x.shape
    block = min(block, r, c)
    pr, pc = (-r) % block, (-c) % block
    if pr or pc:
        x = jnp.pad(x, ((0, pr), (0, pc)))
    out = transpose_kernel(x, block=block, interpret=_interpret())
    return out[:c, :r]


def spmv_ell(
    vals: jax.Array,
    cols: jax.Array,
    x: jax.Array,
    row_block: int = 8,
    impl: str = "pallas",
) -> jax.Array:
    if impl == "ref":
        return ref.spmv_ell(vals, cols, x)
    (vals_p, r) = _pad_rows(vals, row_block)
    (cols_p, _) = _pad_rows(cols, row_block)
    y = spmv_ell_kernel(vals_p, cols_p, x, row_block=row_block, interpret=_interpret())
    return y[:r]


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    impl: str = "pallas",
) -> jax.Array:
    """Flash attention.  impl='pallas' is fully trainable: the backward is
    the FlashAttention-2-style kernel pair (custom_vjp; lse saved, p
    recomputed blockwise — validated against autodiff in tests)."""
    if impl == "ref":
        return ref.mha(q, k, v, causal=causal, window=window, scale=scale)
    b, h, sq, d = q.shape
    skv = k.shape[2]
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    pq, pk = (-sq) % bq, (-skv) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    out = _flash_vjp(q, k, v, causal, window, scale, bq, bk)
    # NB: padded KV columns are masked via kv_len inside the kernel.
    return out[:, :, :sq, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_vjp(q, k, v, causal, window, scale, bq, bk):
    return flash_attention_kernel(
        q, k, v, causal=causal, window=window, scale=scale,
        block_q=bq, block_k=bk, interpret=_interpret(),
    )


def _flash_vjp_fwd(q, k, v, causal, window, scale, bq, bk):
    from .flash_attention import flash_attention_fwd_kernel

    o, lse = flash_attention_fwd_kernel(
        q, k, v, causal=causal, window=window, scale=scale,
        block_q=bq, block_k=bk, interpret=_interpret(),
    )
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, window, scale, bq, bk, res, do):
    from .flash_attention import flash_attention_bwd_kernel

    q, k, v, o, lse = res
    dq, dk, dv = flash_attention_bwd_kernel(
        q, k, v, o, lse, do, causal=causal, window=window, scale=scale,
        block_q=bq, block_k=bk, interpret=_interpret(),
    )
    return dq, dk, dv


_flash_vjp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def paged_decode_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    impl: str = "pallas",
) -> jax.Array:
    """Decode one token per sequence against the paged pool.

    ``k_scale``/``v_scale`` opt into the int8 pool layout: pages hold int8
    codes and each ``(P, page, KVH)`` scale pool holds one fp32 scale per
    page token slot per KV head (``ref.quantize_kv`` on the write side).
    ``impl='pallas'`` dequantizes page-by-page in VMEM; ``impl='ref'``
    dequantizes the whole pool up front through the shared
    :func:`repro.kernels.ref.dequantize_pages` broadcast rule and runs the
    full-precision oracle.
    """
    if impl == "ref":
        if k_scale is not None:
            k_pages = ref.dequantize_pages(k_pages, k_scale)
            v_pages = ref.dequantize_pages(v_pages, v_scale)
        return ref.paged_decode_attention(
            q, k_pages, v_pages, page_table, lengths, scale=scale
        )
    return paged_decode_attention_kernel(
        q, k_pages, v_pages, page_table, lengths,
        k_scale=k_scale, v_scale=v_scale, scale=scale, interpret=_interpret(),
    )


def paged_prefill_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    ctx_rows: jax.Array,
    starts: jax.Array,
    counts: jax.Array,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    impl: str = "pallas",
) -> jax.Array:
    """Causal chunk attention for batched prefill, straight from the pool.

    ``impl='pallas'`` streams each row's context pages through the scalar-
    prefetch indirect path with an online softmax (no gathered context or
    dense score tensor in HBM; GQA grouped in-kernel); ``impl='ref'`` is the
    dense gather + einsum oracle (the pre-kernel serving path).  Rows with
    ``counts == 0`` produce zeros under both.

    ``k_scale``/``v_scale`` opt into the int8 pool layout (same contract as
    :func:`paged_decode_attention`): ``(P, page, KVH)`` fp32 scale pools, one
    scale per page token slot per KV head.  The kernel dequantizes each
    context page in VMEM right after its DMA (fp32 accumulation, identical
    online-softmax structure); the ref path dequantizes the whole pool
    through the shared :func:`repro.kernels.ref.dequantize_pages` rule.
    """
    if impl == "ref":
        if k_scale is not None:
            k_pages = ref.dequantize_pages(k_pages, k_scale)
            v_pages = ref.dequantize_pages(v_pages, v_scale)
        return ref.paged_prefill_attention(
            q, k_pages, v_pages, ctx_rows, starts, counts, scale=scale
        )
    return paged_prefill_attention_kernel(
        q, k_pages, v_pages, ctx_rows, starts, counts,
        k_scale=k_scale, v_scale=v_scale, scale=scale,
        interpret=_interpret(),
    )


def paged_verify(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    ctx_rows: jax.Array,
    lengths: jax.Array,
    counts: jax.Array,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    impl: str = "pallas",
) -> jax.Array:
    """Score K speculative tokens per sequence in one clamped page walk.

    q:       (B, K, H, D) verify queries — the feed token at position 0,
             draft tokens after it; query ``i`` of row ``r`` sits at
             absolute position ``lengths[r] + i``
    counts:  (B,) valid queries per row (0..K); 0 = padding row, zero out

    The speculative-decoding verify step: K causal queries amortize one
    indirect page walk that plain decode would repeat K times.  A verify
    chunk is a prefill chunk appended at the context tail, so both impls
    share the prefill code paths with ``starts = lengths`` (the Pallas
    kernel reuses the clamped scalar-prefetch walk + online softmax; the
    oracle the dense gather + einsum), and ``k_scale``/``v_scale`` opt
    into the same int8 pool layout.  Acceptance is separate — see
    :func:`speculative_accept`.
    """
    if impl == "ref":
        if k_scale is not None:
            k_pages = ref.dequantize_pages(k_pages, k_scale)
            v_pages = ref.dequantize_pages(v_pages, v_scale)
        return ref.paged_verify_attention(
            q, k_pages, v_pages, ctx_rows, lengths, counts, scale=scale
        )
    return paged_verify_attention_kernel(
        q, k_pages, v_pages, ctx_rows, lengths, counts,
        k_scale=k_scale, v_scale=v_scale, scale=scale,
        interpret=_interpret(),
    )


def speculative_accept(
    drafts: jax.Array, greedy: jax.Array, counts: jax.Array
) -> jax.Array:
    """Greedy first-mismatch acceptance: how many verify tokens to emit.

    Pure jnp (no kernel needed — it's O(B·K) int math) and shared by both
    impls so accept/reject stays on device; see
    :func:`repro.kernels.ref.speculative_accept` for the contract.
    """
    return ref.speculative_accept(drafts, greedy, counts)


def paged_kv_append(
    k_pages: jax.Array,
    v_pages: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    active: Optional[jax.Array] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    impl: str = "pallas",
):
    """Append one KV token per sequence into the paged pool.

    ``impl='pallas'`` routes the writes through the packed indirect-scatter
    converter kernel over the row-flattened pool (one indirect write burst
    per K and V); ``impl='ref'`` is the plain XLA scatter oracle.  Both drop
    inactive sequences by routing their index out of bounds.

    Passing ``k_scale``/``v_scale`` — the ``(P, page, KVH)`` fp32 scale pools
    of an int8 KV pool — turns this into *quantize-on-write*: the new rows
    are quantized per (token, kv-head) over ``D`` (``ref.quantize_kv``), the
    int8 codes scatter into the pages and the scales into the scale pools
    through the **same** flat indices (the AXI-Pack picture: the value
    stream plus its sideband metadata share one descriptor; the sideband
    itself is written by :func:`_scatter_scales`).  Returns
    ``(k_pages, v_pages, new_lengths)``, plus ``(k_scale, v_scale)``
    appended when quantizing.
    """
    if impl == "ref":
        return ref.paged_kv_append(
            k_pages, v_pages, k_new, v_new, page_table, lengths, active,
            k_scale=k_scale, v_scale=v_scale,
        )
    p, page, kvh, d = k_pages.shape
    quantized = k_scale is not None
    if quantized:
        k_new, k_s = ref.quantize_kv(k_new)
        v_new, v_s = ref.quantize_kv(v_new)
    slot = lengths // page
    off = lengths % page
    n_pages = page_table.shape[1]
    pids = jnp.take_along_axis(
        page_table, jnp.clip(slot, 0, n_pages - 1)[:, None], axis=1
    )[:, 0]
    flat_idx = (pids * page + off).astype(jnp.int32)
    if active is None:
        active = jnp.ones_like(lengths, dtype=bool)
    # Inactive rows — and rows whose append position falls past their table
    # row (the oracle's ``mode='drop'`` case: an un-clamped out-of-bounds
    # gather would otherwise alias a real page) — target row ``p * page``,
    # one past the pool, which the scatter converter drops.
    flat_idx = jnp.where(
        active & (lengths < n_pages * page), flat_idx, p * page
    )

    def write(pool, new):
        row = pool.shape[2:]
        flat = pool.reshape((p * page,) + row)
        out = indirect_scatter(flat, new.reshape((-1,) + row), flat_idx,
                               impl=impl)
        return out.reshape(pool.shape)

    k_pages = write(k_pages, k_new)
    v_pages = write(v_pages, v_new)
    new_len = lengths + active.astype(lengths.dtype)
    if quantized:
        return (k_pages, v_pages, new_len,
                _scatter_scales(k_scale, flat_idx, k_s),
                _scatter_scales(v_scale, flat_idx, v_s))
    return k_pages, v_pages, new_len


def _scatter_scales(pool: jax.Array, flat_idx: jax.Array,
                    new: jax.Array) -> jax.Array:
    """Write per-token scale rows into a ``(P, page, KVH)`` scale pool.

    A token's scales are ``KVH`` fp32 (16 B at KVH=4), far narrower than a
    row Mosaic can DMA (128 lanes): through the converters every write would
    move a padded copy of the whole pool.  XLA's scatter updates the
    (donated) pool in place at the same flat indices instead, dropping
    out-of-range ones like the converters do.  It indexes the 3-D pool:
    flattening ``(P, page)`` would change the tiled ``(page, KVH)`` minor
    dims and so relayout-copy the pool.
    """
    page = pool.shape[1]
    return pool.at[flat_idx // page, flat_idx % page].set(
        new.reshape((-1,) + pool.shape[2:]), mode="drop")


def paged_kv_write_chunk(
    k_pages: jax.Array,
    v_pages: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    rows: jax.Array,
    starts: jax.Array,
    counts: jax.Array,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    impl: str = "pallas",
):
    """Batched chunked-prefill write, bounded by the pages the chunk touches.

    ``impl='ref'`` is the full-pool scatter oracle.  ``impl='pallas'`` never
    materializes an O(pool) intermediate: each sequence's chunk spans at most
    ``W = ceil(C/page) + 1`` pages, so the converter path gathers those W
    pages per sequence (one packed indirect read), scatters the chunk's rows
    into the gathered window, and writes the touched pages back (one packed
    indirect write) — R·W pages of traffic instead of the whole pool.
    Window slots that cover no valid token are routed out of bounds on the
    way back so a stale copy can never clobber another sequence's page.

    Passing ``k_scale``/``v_scale`` — ``(P, page, KVH)`` fp32 scale pools —
    turns this into *quantize-on-write* (same contract as
    :func:`paged_kv_append`): the chunk is quantized per (token, kv-head)
    over ``D``, the int8 codes go through the window gather/scatter above
    and the scales straight into the scale pools (:func:`_scatter_scales`)
    at the pool slots of the same window pages, with the same out-of-bounds
    routing.  Returns ``(k_pages, v_pages)``, plus ``(k_scale, v_scale)``
    appended when quantizing.
    """
    if impl == "ref":
        return ref.paged_kv_write_chunk(
            k_pages, v_pages, k_new, v_new, rows, starts, counts,
            k_scale=k_scale, v_scale=v_scale,
        )
    p, page, kvh, d = k_pages.shape
    r, c = k_new.shape[:2]
    n_pages = rows.shape[1]
    quantized = k_scale is not None
    if quantized:
        k_new, k_s = ref.quantize_kv(k_new)
        v_new, v_s = ref.quantize_kv(v_new)
    w = -(-c // page) + 1
    p_lo = starts // page                                         # (R,)
    lp = p_lo[:, None] + jnp.arange(w, dtype=jnp.int32)           # (R, W)
    pids = jnp.take_along_axis(
        rows, jnp.clip(lp, 0, n_pages - 1), axis=1
    )                                                             # (R, W)
    # A window slot is real iff it covers >= 1 valid token of its sequence.
    p_hi = (starts + jnp.maximum(counts - 1, 0)) // page
    real = (lp <= p_hi[:, None]) & (counts[:, None] > 0) & (lp < n_pages)
    # Local scatter index of token (r, c) inside the (R, W, page) window.
    pos = starts[:, None] + jnp.arange(c, dtype=jnp.int32)        # (R, C)
    valid = jnp.arange(c, dtype=jnp.int32)[None, :] < counts[:, None]
    wp = pos // page - p_lo[:, None]                              # (R, C)
    loc = (jnp.arange(r, dtype=jnp.int32)[:, None] * w + wp) * page + pos % page
    loc = jnp.where(valid, loc, r * w * page).reshape(-1)

    def write(pool, new):
        row = pool.shape[2:]
        win = indirect_gather(
            pool, jnp.clip(pids, 0, p - 1).reshape(-1), impl=impl
        )                                                     # (R*W, page, *row)
        win = indirect_scatter(
            win.reshape((r * w * page,) + row), new.reshape((-1,) + row), loc,
            impl=impl,
        )
        # Window slots that cover no valid token target page ``p`` (one past
        # the pool), which the scatter converter drops.
        return indirect_scatter(
            pool, win.reshape((r * w, page) + row),
            jnp.where(real, pids, p).reshape(-1), impl=impl,
        )

    kp = write(k_pages, k_new)
    vp = write(v_pages, v_new)
    if quantized:
        # Token (r, c)'s pool slot: its window page, kept only where that
        # page is written back above.
        tok_pid = jnp.take_along_axis(pids, wp, axis=1)           # (R, C)
        tok_real = jnp.take_along_axis(real, wp, axis=1)
        flat = jnp.where(valid & tok_real, tok_pid * page + pos % page,
                         p * page).reshape(-1)
        return (kp, vp, _scatter_scales(k_scale, flat, k_s),
                _scatter_scales(v_scale, flat, v_s))
    return kp, vp


# ---------------------------------------------------------------------------
# MoE packed dispatch / combine (composite over the indirect converters)
# ---------------------------------------------------------------------------


def moe_dispatch(
    tokens: jax.Array,
    expert_idx: jax.Array,
    num_experts: int,
    capacity: int,
    impl: str = "pallas",
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Pack tokens into (E, C, D) expert buffers via an indirect stream.

    Slot computation (ranking within expert) is cheap int arithmetic; the
    heavy data movement — scattering token rows into expert-contiguous
    buffers — is one packed indirect write.
    """
    if impl == "ref":
        return ref.moe_dispatch(tokens, expert_idx, num_experts, capacity)
    t, d = tokens.shape
    k = expert_idx.shape[1]
    flat_e = expert_idx.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, num_experts, dtype=jnp.int32)
    pos_in_e = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot - onehot, axis=1)
    keep = pos_in_e < capacity
    slot = jnp.where(keep, flat_e * capacity + pos_in_e, num_experts * capacity)

    tok_rep = jnp.repeat(tokens, k, axis=0)
    buf = jnp.zeros((num_experts * capacity + 1, d), tokens.dtype)
    buf = indirect_scatter(buf, tok_rep, slot, impl=impl)[:-1]

    src = jnp.full((num_experts * capacity + 1,), -1, jnp.int32)
    src = src.at[slot].set(jnp.arange(t * k, dtype=jnp.int32))[:-1]
    return (
        buf.reshape(num_experts, capacity, d),
        src.reshape(num_experts, capacity),
        keep.reshape(t, k),
    )


def moe_combine(
    outputs: jax.Array,
    src_index: jax.Array,
    gate_weights: jax.Array,
    num_tokens: int,
    impl: str = "pallas",
) -> jax.Array:
    """Un-pack expert outputs to token order (indirect gather) + gate-weight."""
    if impl == "ref":
        return ref.moe_combine(outputs, src_index, gate_weights, num_tokens)
    e, c, d = outputs.shape
    k = gate_weights.shape[1]
    flat_out = outputs.reshape(e * c, d)
    flat_src = src_index.reshape(e * c)
    # Invert the dispatch permutation: for each (token, k) slot find its
    # expert-buffer position, then gather — one packed indirect read.
    inv = jnp.full((num_tokens * k + 1,), e * c, jnp.int32)
    inv = inv.at[jnp.where(flat_src >= 0, flat_src, num_tokens * k)].set(
        jnp.arange(e * c, dtype=jnp.int32)
    )[:-1]
    flat_out_ext = jnp.pad(flat_out, ((0, 1), (0, 0)))  # row e*c = zeros (dropped)
    contrib = indirect_gather(flat_out_ext, inv, impl=impl)
    contrib = contrib.reshape(num_tokens, k, d)
    return jnp.einsum("tkd,tk->td", contrib, gate_weights.astype(outputs.dtype))
