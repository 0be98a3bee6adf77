"""Paged-KV prefill attention kernel — flash-style chunk attention over pages.

The read side of batched chunked prefill, as an indirect packed stream: each
pending sequence's context lives in scattered physical pages, and its page-
table row is the memory-resident index vector of an AXI-Pack indirect burst.
Here (as in :mod:`repro.kernels.paged_decode`) the table rides the scalar-
prefetch channel and the BlockSpec ``index_map`` turns each entry into one
direct HBM→VMEM page DMA — the chunk's queries stream over their context one
page at a time with an online (flash) softmax, so neither the gathered
``(R, ctx·page, KVH, D)`` context nor the ``(R, C, H, ctx·page)`` score
tensor is ever materialized in HBM.  GQA is handled by grouping queries per
KV head inside the kernel (the group mapping never repeats K/V).

The page walk is *length-adaptive* exactly like decode: per-row context page
counts (``ceil((start + count) / page)``) are prefetched, and every grid step
past a row's last context page is clamped to that page — revisited blocks are
not re-fetched, so unmapped tail pages (short rows in a wide-bucket batch)
issue no DMAs, and out-of-context compute is skipped by the
``j·page < ctx_len`` predicate.  ``counts == 0`` padding rows clamp their
entire walk to the row's first table entry — at most one warm-up page fetch
(as with decode's empty sequences), never the tail — and output exact zeros.

Supports int8-quantized pools exactly like decode: per-(page-token,
kv-head) scale pages ride the same clamped index map as their K/V pages
and dequant happens in VMEM before the fp32 accumulation — narrower
elements packed onto the page stream, the paper's §III-E element-size
argument (8-bit elements quadruple the FP32 packing factor).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # finite sentinel: keeps exp() NaN-free on fully-masked rows


def _prefill_body(
    # scalar prefetch
    page_table_ref,   # (R * ctx_pages,) physical page ids
    starts_ref,       # (R,) absolute position of each row's tokens[0]
    counts_ref,       # (R,) valid tokens per row (0 = padding row)
    used_ref,         # (R,) context-page count per row (ceil(ctx_len/page))
    # inputs
    q_ref,            # (1, C, H, D)
    k_ref,            # (1, page, KVH, D) — int8 codes when quantized
    v_ref,
    k_scale_ref,      # (1, page, KVH) fp32 or None
    v_scale_ref,
    # output
    o_ref,            # (1, C, H, D)
    # scratch
    m_ref,            # (C*H, 128) running max
    l_ref,            # (C*H, 128) running denominator
    acc_ref,          # (C*H, D)   running numerator
    *,
    page: int,
    ctx_pages: int,
    c: int,
    kvh: int,
    rep: int,
    d: int,
    scale: float,
    quantized: bool,
):
    r = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = starts_ref[r]
    count = counts_ref[r]
    # Padding rows (count == 0) have a zero context bound regardless of
    # ``start``: every block is skipped and the output stays exact zeros.
    ctx_len = jnp.where(count > 0, start + count, 0)

    @pl.when(j * page < ctx_len)
    def _update():
        k = k_ref[0].astype(jnp.float32)                  # (page, KVH, D)
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            # Dequant in VMEM right after the page DMA (the narrow elements
            # travelled the bus packed; same broadcast as dequantize_pages).
            k = k * k_scale_ref[0].astype(jnp.float32)[..., None]
            v = v * v_scale_ref[0].astype(jnp.float32)[..., None]
        q = q_ref[0].astype(jnp.float32)                  # (C, H, D)
        # Group queries per KV head: row (g, ci*rep + u) is query position ci
        # of head g*rep + u — GQA without materializing repeated K/V.
        qg = (q.reshape(c, kvh, rep, d)
              .transpose(1, 0, 2, 3)
              .reshape(kvh, c * rep, d))
        s = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32,
        ) * scale                                         # (KVH, C*rep, page)
        kv_pos = j * page + jax.lax.broadcasted_iota(
            jnp.int32, (kvh, c * rep, page), 2
        )
        q_pos = start + jax.lax.broadcasted_iota(
            jnp.int32, (kvh, c * rep, page), 1
        ) // rep
        mask = (kv_pos <= q_pos) & (kv_pos < ctx_len)
        s = jnp.where(mask, s, NEG_INF)

        rows = c * kvh * rep                              # == C * H
        s_f = s.reshape(rows, page)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s_f, axis=1, keepdims=True))
        p = jnp.where(mask.reshape(rows, page), jnp.exp(s_f - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(
            l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True),
            l_ref.shape,
        )
        # acc update: p (KVH, C*rep, page) × v (page, KVH, D) → (KVH, C*rep, D)
        pv = jax.lax.dot_general(
            p.reshape(kvh, c * rep, page), v,
            (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv.reshape(rows, d)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == ctx_pages - 1)
    def _finalize():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        out = (acc_ref[...] / l).reshape(kvh, c, rep, d).transpose(1, 0, 2, 3)
        o_ref[0] = out.reshape(c, kvh * rep, d).astype(o_ref.dtype)


def paged_prefill_attention_kernel(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    ctx_rows: jax.Array,
    starts: jax.Array,
    counts: jax.Array,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Causal chunk attention for one batched prefill step over a paged pool.

    q:          (R, C, H, D)  chunk queries; row r's query ``c`` sits at
                absolute position ``starts[r] + c``
    k/v_pages:  (P, page, KVH, D) physical page pool (the chunk's K/V rows
                must already be written — attention runs after the chunk
                write, as in the serve engine); int8 codes when
                ``k_scale``/``v_scale`` given
    ctx_rows:   (R, ctx_pages) int32 leading page-table entries per row
    starts:     (R,) int32 absolute position of tokens[0]
    counts:     (R,) int32 valid tokens per row; ``counts[r] == 0`` rows are
                padding and produce zero output (compute predicated off, the
                walk clamped to the row's first table entry — at most one
                warm-up page fetch, no NaNs)
    k/v_scale:  optional (P, page, KVH) fp32 scale pools (one scale per page
                token slot per KV head).  Each scale page rides the same
                clamped index map as its K/V page — one extra narrow DMA per
                grid step — and the dequant happens in VMEM before the fp32
                flash accumulation, so the online-softmax structure is
                unchanged.

    Query ``c`` of row ``r`` attends positions ``0 .. starts[r] + c`` capped
    at the row's written context (``starts[r] + counts[r]`` tokens), with an
    online softmax accumulated over one grid step per context page.
    """
    r, c, h, d = q.shape
    _, page, kvh, _ = k_pages.shape
    ctx_pages = ctx_rows.shape[1]
    rep = h // kvh
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    quantized = k_scale is not None

    flat_table = ctx_rows.reshape(-1).astype(jnp.int32)
    starts = starts.astype(jnp.int32)
    counts = counts.astype(jnp.int32)
    # Padding rows clamp their whole walk to the first table entry (one
    # revisited block, like decode's empty sequences) — no tail DMAs.
    used = jnp.where(
        counts > 0, jnp.maximum(-(-(starts + counts) // page), 1), 1
    ).astype(jnp.int32)

    def table_idx(r_, j, pt_ref, st_ref, ct_ref, used_ref):
        jj = jnp.minimum(j, used_ref[r_] - 1)
        return (pt_ref[r_ * ctx_pages + jj], 0, 0, 0)

    def scale_idx(r_, j, pt_ref, st_ref, ct_ref, used_ref):
        jj = jnp.minimum(j, used_ref[r_] - 1)
        return (pt_ref[r_ * ctx_pages + jj], 0, 0)

    q_idx = lambda r_, j, pt, st, ct, us: (r_, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, c, h, d), q_idx),
        pl.BlockSpec((1, page, kvh, d), table_idx),
        pl.BlockSpec((1, page, kvh, d), table_idx),
    ]
    args = [q, k_pages, v_pages]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, page, kvh), scale_idx),
            pl.BlockSpec((1, page, kvh), scale_idx),
        ]
        args += [k_scale, v_scale]

    body = functools.partial(
        _prefill_body,
        page=page,
        ctx_pages=ctx_pages,
        c=c,
        kvh=kvh,
        rep=rep,
        d=d,
        scale=scale,
        quantized=quantized,
    )
    if not quantized:
        body = functools.partial(_drop_scale_refs, body)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(r, ctx_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, c, h, d), q_idx),
        scratch_shapes=[
            pltpu.VMEM((c * h, 128), jnp.float32),
            pltpu.VMEM((c * h, 128), jnp.float32),
            pltpu.VMEM((c * h, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, c, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        name="paged_prefill",
        interpret=interpret,
    )(flat_table, starts, counts, used, *args)


def _drop_scale_refs(body, pt, st, ct, us, q_ref, k_ref, v_ref, o_ref, m_ref,
                     l_ref, acc_ref):
    return body(pt, st, ct, us, q_ref, k_ref, v_ref, None, None, o_ref,
                m_ref, l_ref, acc_ref)
