"""Pure-jnp oracle implementations for every Pallas kernel in this package.

Each function is the mathematical ground truth the kernels are validated
against (``tests/kernels`` sweeps shapes/dtypes and asserts allclose).  They
are also the CPU fall-back path used by the models when
``use_pallas=False`` and the source of the differentiable reference
semantics (kernels that need gradients wire these in through custom_vjp or
are used forward-only).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Packed stream converters (the paper's four irregular converters)
# ---------------------------------------------------------------------------


def strided_gather(src: jax.Array, base: int, stride: int, count: int) -> jax.Array:
    """out[k] = src[base + k*stride]; src (N, row), out (count, row)."""
    idx = base + stride * jnp.arange(count)
    return jnp.take(src, idx, axis=0, mode="clip")


def strided_scatter(
    dst: jax.Array, packed: jax.Array, base: int, stride: int
) -> jax.Array:
    """dst[base + k*stride] = packed[k]."""
    idx = base + stride * jnp.arange(packed.shape[0])
    return dst.at[idx].set(packed)


def indirect_gather(src: jax.Array, indices: jax.Array) -> jax.Array:
    """out[k] = src[indices[k]]; indices memory-resident (vlimxei semantics)."""
    return jnp.take(src, indices, axis=0, mode="clip")


def indirect_scatter(
    dst: jax.Array, packed: jax.Array, indices: jax.Array, mode: str = "set"
) -> jax.Array:
    """dst[indices[k]] = packed[k] (or += for mode='add')."""
    at = dst.at[indices]
    return at.add(packed) if mode == "add" else at.set(packed)


# ---------------------------------------------------------------------------
# Tiled in-situ matrix transpose (ismt benchmark)
# ---------------------------------------------------------------------------


def tiled_transpose(x: jax.Array) -> jax.Array:
    return jnp.swapaxes(x, -1, -2)


# ---------------------------------------------------------------------------
# Sparse matrix-vector product (spmv / prank / sssp benchmarks)
# ---------------------------------------------------------------------------


def spmv_ell(vals: jax.Array, cols: jax.Array, x: jax.Array) -> jax.Array:
    """ELL-format SpMV: y[r] = sum_k vals[r,k] * x[cols[r,k]].

    Padding entries carry ``vals == 0`` (their column index is arbitrary but
    in-range), so they contribute nothing.
    """
    xg = jnp.take(x, cols, axis=0, mode="clip")
    return jnp.sum(vals * xg, axis=-1)


def csr_to_ell(
    indptr: np.ndarray, indices: np.ndarray, data: np.ndarray, n_rows: int,
    pad_to: Optional[int] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Convert CSR arrays to padded ELL (vals, cols) numpy arrays."""
    counts = np.diff(indptr)
    k = int(counts.max()) if pad_to is None else pad_to
    vals = np.zeros((n_rows, k), dtype=data.dtype)
    cols = np.zeros((n_rows, k), dtype=indices.dtype)
    for r in range(n_rows):
        lo, hi = indptr[r], indptr[r + 1]
        vals[r, : hi - lo] = data[lo:hi]
        cols[r, : hi - lo] = indices[lo:hi]
    return vals, cols


# ---------------------------------------------------------------------------
# Attention (training/prefill flash + decode)
# ---------------------------------------------------------------------------


def _attn_mask(
    q_len: int,
    kv_len: int,
    causal: bool,
    window: Optional[int],
    q_offset: int = 0,
) -> jax.Array:
    """(q_len, kv_len) boolean mask; True = attend."""
    qi = q_offset + jnp.arange(q_len)[:, None]
    kj = jnp.arange(kv_len)[None, :]
    m = jnp.ones((q_len, kv_len), dtype=bool)
    if causal:
        m &= qi >= kj
    if window is not None:
        m &= qi - kj < window
    return m


def mha(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    scale: Optional[float] = None,
) -> jax.Array:
    """Reference attention. q (B,H,S,D); k,v (B,KVH,Skv,D); GQA by repeat.

    ``window`` is the sliding-window size (gemma3-style local attention);
    ``q_offset`` positions queries relative to keys (decode/prefill chunking).
    """
    b, h, sq, d = q.shape
    kvh = k.shape[1]
    if kvh != h:
        rep = h // kvh
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    mask = _attn_mask(sq, k.shape[2], causal, window, q_offset)
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    # Fully-masked rows (can happen in padded decode) produce NaN; zero them.
    w = jnp.where(jnp.isnan(w), 0.0, w)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v.astype(jnp.float32)).astype(q.dtype)


def paged_decode_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    scale: Optional[float] = None,
) -> jax.Array:
    """Decode attention over a paged KV cache (oracle).

    q:          (B, H, D)           one new query token per sequence
    k/v_pages:  (P, page, KVH, D)   global physical page pool
    page_table: (B, pages_per_seq)  int32 physical page ids (indirect stream)
    lengths:    (B,)                current KV length per sequence
    """
    b, h, d = q.shape
    pages_per_seq = page_table.shape[1]
    page = k_pages.shape[1]
    kvh = k_pages.shape[2]
    # Gather each sequence's logical KV: (B, pages_per_seq, page, KVH, D)
    kg = jnp.take(k_pages, page_table, axis=0)
    vg = jnp.take(v_pages, page_table, axis=0)
    skv = pages_per_seq * page
    kg = kg.reshape(b, skv, kvh, d).transpose(0, 2, 1, 3)
    vg = vg.reshape(b, skv, kvh, d).transpose(0, 2, 1, 3)
    rep = h // kvh
    kg = jnp.repeat(kg, rep, axis=1)
    vg = jnp.repeat(vg, rep, axis=1)
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    logits = jnp.einsum("bhd,bhkd->bhk", q, kg).astype(jnp.float32) * scale
    mask = jnp.arange(skv)[None, :] < lengths[:, None]
    logits = jnp.where(mask[:, None, :], logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    w = jnp.where(jnp.isnan(w), 0.0, w)
    return jnp.einsum("bhk,bhkd->bhd", w, vg.astype(jnp.float32)).astype(q.dtype)


def paged_prefill_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    ctx_rows: jax.Array,
    starts: jax.Array,
    counts: jax.Array,
    scale: Optional[float] = None,
) -> jax.Array:
    """Causal chunk attention over a paged KV pool (oracle, dense einsum).

    q:         (R, C, H, D)    chunk queries; row r's query ``c`` is at
               absolute position ``starts[r] + c``
    k/v_pages: (P, page, KVH, D) physical pool (chunk K/V already written)
    ctx_rows:  (R, ctx_pages)  leading page-table entries per row
    starts/counts: (R,) int32; ``counts[r] == 0`` rows are padding

    Gathers each row's bounded context densely, repeats K/V for GQA, and
    masks with a *finite* constant (``jnp.finfo.min``) so fully-masked rows
    (padding rows, degenerate starts) can never produce NaN softmax outputs
    — their weights are zeroed instead, matching the kernel's skipped
    blocks.  This is the pre-kernel serving prefill path, kept verbatim as
    the ground truth the Pallas kernel is validated against.
    """
    r, c, h, d = q.shape
    page = k_pages.shape[1]
    kvh = k_pages.shape[2]
    ctx_pages = ctx_rows.shape[1]
    skv = ctx_pages * page
    kg = jnp.take(k_pages, ctx_rows.reshape(-1), axis=0).reshape(
        r, skv, kvh, d
    )
    vg = jnp.take(v_pages, ctx_rows.reshape(-1), axis=0).reshape(
        r, skv, kvh, d
    )
    rep = h // kvh
    kg = jnp.repeat(kg, rep, axis=2)                       # (R, S, H, D)
    vg = jnp.repeat(vg, rep, axis=2)
    pos = starts[:, None] + jnp.arange(c, dtype=jnp.int32)  # (R, C)
    kv_pos = jnp.arange(skv, dtype=jnp.int32)
    # Padding rows (counts == 0) are fully masked regardless of ``starts``:
    # their context bound is forced to zero, so they output exact zeros.
    live = jnp.where(counts > 0, starts + counts, 0)
    mask = (kv_pos[None, None, :] <= pos[:, :, None]) & (
        kv_pos[None, None, :] < live[:, None, None]
    )                                                      # (R, C, S)
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    s = jnp.einsum("rchd,rshd->rchs", q, kg).astype(jnp.float32) * scale
    s = jnp.where(mask[:, :, None, :], s, jnp.finfo(s.dtype).min)
    w = jax.nn.softmax(s, axis=-1)
    w = jnp.where(mask[:, :, None, :], w, 0.0)
    out = jnp.einsum("rchs,rshd->rchd", w, vg.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_verify_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    ctx_rows: jax.Array,
    lengths: jax.Array,
    counts: jax.Array,
    scale: Optional[float] = None,
) -> jax.Array:
    """Multi-query speculative verify attention (oracle, dense einsum).

    q:         (B, K, H, D)   verify queries — query ``i`` of row ``r`` is
               the token at absolute position ``lengths[r] + i``
    k/v_pages: (P, page, KVH, D) physical pool (the K query tokens' K/V
               already written, append-then-attend as in prefill)
    ctx_rows:  (B, ctx_pages) leading page-table entries per row
    lengths:   (B,) context tokens per row *before* this verify chunk
    counts:    (B,) valid query tokens per row (0 = padding row, zero out)

    A verify chunk is a causal prefill chunk appended at the context tail,
    so the oracle *is* :func:`paged_prefill_attention` with
    ``starts = lengths`` — one definition, shared bit-for-bit with the
    serving prefill path.
    """
    return paged_prefill_attention(
        q, k_pages, v_pages, ctx_rows, lengths, counts, scale=scale
    )


def speculative_accept(
    drafts: jax.Array, greedy: jax.Array, counts: jax.Array
) -> jax.Array:
    """Greedy accept/reject for speculative decoding (on-device, exact).

    drafts: (B, K-1) int32 draft tokens d_1..d_{K-1} (positions after the
            feed token)
    greedy: (B, K)   int32 argmax of the verify logits at every position
            (``greedy[:, i]`` is the model's true next token after
            position ``lengths + i``)
    counts: (B,)     int32 query tokens actually scored per row (0..K;
            capacity clamping / inactive rows give 0)

    Returns ``n_emit`` (B,) int32 — how many of the K scored tokens are
    *emitted* per row: the accepted draft prefix plus the model's one
    bonus token, capped at ``counts``.  Draft ``i`` is accepted iff every
    draft before it matched too (first-mismatch truncation):

        a      = Σ_i  Π_{j<=i} [drafts[j] == greedy[j]]
        n_emit = min(a + 1, counts)

    With K == 1 (no drafts) this is ``min(1, counts)`` — plain decode.
    Greedy acceptance is exact: the emitted tokens ``greedy[:, :n_emit]``
    are bitwise the tokens non-speculative decode would have produced.
    """
    match = (drafts == greedy[:, : drafts.shape[1]]).astype(jnp.int32)
    a = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
    return jnp.minimum(a + 1, counts).astype(jnp.int32)


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
):
    """Append one KV token per sequence into the paged pool (oracle).

    The write side of the paged indirect stream: each sequence scatters its
    new K/V row to ``(page_table[b, len_b // page], len_b % page)``.

    k/v_pages:  (P, page, KVH, D) physical pool
    k/v_new:    (B, KVH, D)       one new token per sequence
    page_table: (B, pages_per_seq) int32; lengths: (B,) int32
    active:     (B,) bool — inactive sequences write nothing and keep their
                length (their scatter is routed out of bounds and dropped).
    k/v_scale:  optional (P, page, KVH) fp32 scale pools (the int8 pool
                layout — see :func:`quantize_kv`).  When given, ``k_new`` /
                ``v_new`` are quantized on write: the int8 codes land in the
                pages, the per-(page-token, kv-head) scales in the scale
                pools, through the *same* scatter indices.

    Returns ``(k_pages, v_pages, new_lengths)`` — plus ``(k_scale, v_scale)``
    appended when quantizing.
    """
    p, page, _, _ = k_pages.shape
    quantized = k_scale is not None
    if quantized:
        k_new, k_s = quantize_kv(k_new)
        v_new, v_s = quantize_kv(v_new)
    slot = lengths // page
    off = lengths % page
    pids = jnp.take_along_axis(page_table, slot[:, None], axis=1)[:, 0]
    if active is None:
        active = jnp.ones_like(lengths, dtype=bool)
    # Route inactive writes past the pool; scatter mode='drop' discards them.
    pids = jnp.where(active, pids, p)
    k_pages = k_pages.at[pids, off].set(k_new.astype(k_pages.dtype),
                                        mode="drop")
    v_pages = v_pages.at[pids, off].set(v_new.astype(v_pages.dtype),
                                        mode="drop")
    new_len = lengths + active.astype(lengths.dtype)
    if quantized:
        k_scale = k_scale.at[pids, off].set(k_s, mode="drop")
        v_scale = v_scale.at[pids, off].set(v_s, mode="drop")
        return k_pages, v_pages, new_len, k_scale, v_scale
    return k_pages, v_pages, new_len


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
):
    """Scatter one prefill chunk per sequence into the paged pool (oracle).

    The batched write side of chunked prefill: sequence ``r`` writes its
    ``counts[r]`` leading rows of ``k_new[r]``/``v_new[r]`` at absolute
    positions ``starts[r] + c`` through its page-table row ``rows[r]``.

    k/v_pages: (P, page, KVH, D) physical pool
    k/v_new:   (R, C, KVH, D)    chunk of new tokens per sequence
    rows:      (R, n_pages) int32 page-table rows; starts/counts: (R,) int32
    k/v_scale: optional (P, page, KVH) fp32 scale pools.  When given, the
               chunk is quantized on write (:func:`quantize_kv`): int8 codes
               into the pages, per-(page-token, kv-head) scales into the
               scale pools, through the same scatter indices.

    Rows with ``counts[r] == 0`` write nothing (their scatters are routed out
    of bounds and dropped), so the caller can pad the batch freely.

    Returns ``(k_pages, v_pages)`` — plus ``(k_scale, v_scale)`` appended
    when quantizing.
    """
    p, page, kvh, d = k_pages.shape
    r, c = k_new.shape[:2]
    n_pages = rows.shape[1]
    quantized = k_scale is not None
    if quantized:
        k_new, k_s = quantize_kv(k_new)
        v_new, v_s = quantize_kv(v_new)
    pos = starts[:, None] + jnp.arange(c, dtype=jnp.int32)          # (R, C)
    valid = jnp.arange(c, dtype=jnp.int32)[None, :] < counts[:, None]
    pids = jnp.take_along_axis(
        rows, jnp.clip(pos // page, 0, n_pages - 1), axis=1
    )                                                                # (R, C)
    flat = jnp.where(valid, pids * page + pos % page, p * page)
    flat = flat.reshape(-1)
    kf = k_pages.reshape(p * page, kvh, d)
    vf = v_pages.reshape(p * page, kvh, d)
    kf = kf.at[flat].set(k_new.reshape(-1, kvh, d).astype(kf.dtype),
                         mode="drop")
    vf = vf.at[flat].set(v_new.reshape(-1, kvh, d).astype(vf.dtype),
                         mode="drop")
    k_pages = kf.reshape(p, page, kvh, d)
    v_pages = vf.reshape(p, page, kvh, d)
    if quantized:
        ks = k_scale.reshape(p * page, kvh)
        vs = v_scale.reshape(p * page, kvh)
        ks = ks.at[flat].set(k_s.reshape(-1, kvh), mode="drop")
        vs = vs.at[flat].set(v_s.reshape(-1, kvh), mode="drop")
        return (k_pages, v_pages,
                ks.reshape(p, page, kvh), vs.reshape(p, page, kvh))
    return k_pages, v_pages


# ---------------------------------------------------------------------------
# MoE dispatch / combine (packed token routing)
# ---------------------------------------------------------------------------


def moe_dispatch(
    tokens: jax.Array, expert_idx: jax.Array, num_experts: int, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Pack tokens into per-expert buffers (oracle for the packed dispatch).

    tokens:     (T, D) flattened token activations
    expert_idx: (T, K) top-k expert assignment per token
    Returns (buffers (E, C, D), src_index (E, C) original (token*K+k) slot or
    -1 for empty, keep_mask (T, K) whether each assignment was kept).
    """
    t, d = tokens.shape
    k = expert_idx.shape[1]
    flat_e = expert_idx.reshape(-1)                      # (T*K,)
    # Position of each assignment within its expert (stable order).
    onehot = jax.nn.one_hot(flat_e, num_experts, dtype=jnp.int32)  # (TK, E)
    pos = jnp.cumsum(onehot, axis=0) * onehot - onehot   # rank within expert
    pos_in_e = jnp.sum(pos, axis=1)                      # (TK,)
    keep = pos_in_e < capacity
    slot = jnp.where(keep, flat_e * capacity + pos_in_e, num_experts * capacity)
    src = jnp.full((num_experts * capacity + 1,), -1, dtype=jnp.int32)
    src = src.at[slot].set(jnp.arange(t * k, dtype=jnp.int32))[:-1]
    tok_rep = jnp.repeat(tokens, k, axis=0)              # (TK, D)
    buf = jnp.where(
        (src >= 0)[:, None], jnp.take(tok_rep, jnp.maximum(src, 0), axis=0), 0.0
    )
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
) -> jax.Array:
    """Un-pack expert outputs back to token order with gate weighting.

    outputs:      (E, C, D) expert results
    src_index:    (E, C)    original token*K+k slot (or -1)
    gate_weights: (T, K)    router weights
    """
    e, c, d = outputs.shape
    k = gate_weights.shape[1]
    flat_out = outputs.reshape(e * c, d)
    flat_src = src_index.reshape(e * c)
    contrib = jnp.zeros((num_tokens * k, d), dtype=outputs.dtype)
    contrib = contrib.at[jnp.maximum(flat_src, 0)].add(
        jnp.where((flat_src >= 0)[:, None], flat_out, 0.0)
    )
    contrib = contrib.reshape(num_tokens, k, d)
    return jnp.einsum("tkd,tk->td", contrib, gate_weights.astype(outputs.dtype))


# ---------------------------------------------------------------------------
# Int8 packing (gradient compression / quantized KV)
# ---------------------------------------------------------------------------


def int8_quantize(x: jax.Array, axis: int = -1) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-slice int8 quantization: returns (q, scale)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def int8_dequantize(q: jax.Array, scale: jax.Array, dtype=jnp.float32) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-(token, kv-head) int8 quantization of new KV rows.

    ``x`` has shape ``(..., KVH, D)``; each ``(..., kv-head)`` slice is
    quantized symmetrically over its ``D`` components.  Returns the int8
    codes (same shape) and the fp32 scales with the ``D`` axis dropped
    (``(..., KVH)``) — exactly the scale-pool layout the paged kernels
    prefetch (one scale per page token slot per KV head).
    """
    q, scale = int8_quantize(x, axis=-1)
    return q, scale[..., 0]


def dequantize_pages(
    pages: jax.Array, scale: jax.Array, dtype=jnp.float32
) -> jax.Array:
    """Dequantize an int8 page pool with per-(page-token, kv-head) scales.

    ``pages`` is ``(..., page, KVH, D)`` int8; ``scale`` is the matching
    ``(..., page, KVH)`` fp32 pool (no ``D`` axis — one scale per token slot
    per KV head).  The single scale-broadcast rule shared by every ``ref``
    dequant fallback and mirrored element-wise inside the Pallas kernels'
    VMEM dequant, so the oracle and kernel can never disagree on layout.
    """
    return int8_dequantize(pages, scale[..., None], dtype)
