"""Paged-KV decode attention kernel — the flagship indirect-stream application.

A paged KV cache stores sequences as scattered fixed-size physical pages; the
page table is exactly an AXI-Pack *indirect stream descriptor*: a memory-
resident index array resolved near memory.  Here the page table rides the
scalar-prefetch channel and the BlockSpec ``index_map`` turns each entry into
a direct HBM→VMEM page DMA — K/V pages are packed densely into VMEM and the
core never touches an address computation (the paper's element request
generator, verbatim in Pallas).

Supports an int8-quantized KV pool (per-(page-token, kv-head) scales): the
TPU analogue of packing *narrower elements* onto the bus — a quarter of the
fp32 HBM traffic (half of bf16) for the bandwidth-bound decode step,
exactly the paper's element-size argument in §III-E.  The scale pages ride
the same clamped index map as their K/V pages; the write side
(``ops.paged_kv_append`` / ``ops.paged_kv_write_chunk`` with scale pools)
quantizes on write through the same indices.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_body(
    # scalar prefetch
    page_table_ref,   # (B * n_pages,) physical page ids
    lengths_ref,      # (B,) current KV length per sequence
    used_ref,         # (B,) mapped-page count per sequence (ceil(len/page))
    # inputs
    q_ref,            # (1, H, D)
    k_ref,            # (1, page, KVH, D)
    v_ref,
    k_scale_ref,      # (1, page, KVH) or None
    v_scale_ref,
    # output
    o_ref,            # (1, H, D)
    # scratch
    m_ref,
    l_ref,
    acc_ref,
    *,
    page: int,
    n_pages: int,
    kvh: int,
    rep: int,
    d: int,
    scale: float,
    quantized: bool,
):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq_len = lengths_ref[b]

    @pl.when(j * page < seq_len)
    def _update():
        k = k_ref[0].astype(jnp.float32)                  # (page, KVH, D)
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            k = k * k_scale_ref[0].astype(jnp.float32)[..., None]
            v = v * v_scale_ref[0].astype(jnp.float32)[..., None]
        q = q_ref[0].astype(jnp.float32)                  # (H, D)
        qg = q.reshape(kvh, rep, d)
        # scores: (KVH, rep, page)
        s = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (1,))), preferred_element_type=jnp.float32
        ) * scale
        pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (kvh, rep, page), 2)
        mask = pos < seq_len
        s = jnp.where(mask, s, NEG_INF)

        h = kvh * rep
        s_h = s.reshape(h, page)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s_h, axis=1, keepdims=True))
        p = jnp.where(mask.reshape(h, page), jnp.exp(s_h - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(
            l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True), l_ref.shape
        )
        # acc update: p (KVH, rep, page) × v (page, KVH, D) → (KVH, rep, D)
        pv = jax.lax.dot_general(
            p.reshape(kvh, rep, page),
            v,
            (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv.reshape(h, d)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == n_pages - 1)
    def _finalize():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_decode_attention_kernel(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Decode one token per sequence against a paged KV pool.

    q:          (B, H, D)
    k/v_pages:  (P, page, KVH, D) — int8 when ``k_scale``/``v_scale`` given
    page_table: (B, n_pages) int32 physical page ids (pad with 0)
    lengths:    (B,) int32 valid KV length per sequence

    The page walk is *length-adaptive*: per-sequence mapped-page counts ride
    the scalar-prefetch channel alongside the table, and the BlockSpec index
    map clamps every grid step past a sequence's last mapped page to that
    last page.  Revisited blocks are not re-fetched, so fully-unmapped tail
    pages issue no HBM→VMEM DMAs (their compute is already skipped by the
    ``j * page < len`` predicate) — short sequences in a long-table batch
    stream only what they actually own.  The batch grid dimension is
    declared ``parallel`` (sequences are independent); only the page walk is
    ``arbitrary`` (it carries the running softmax state).
    """
    b, h, d = q.shape
    p_tot, page, kvh, _ = k_pages.shape
    n_pages = page_table.shape[1]
    rep = h // kvh
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    quantized = k_scale is not None

    flat_table = page_table.reshape(-1).astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    used = jnp.maximum(-(-lengths // page), 1).astype(jnp.int32)

    def table_idx(b_, j, pt_ref, len_ref, used_ref):
        jj = jnp.minimum(j, used_ref[b_] - 1)
        return (pt_ref[b_ * n_pages + jj], 0, 0, 0)

    def scale_idx(b_, j, pt_ref, len_ref, used_ref):
        jj = jnp.minimum(j, used_ref[b_] - 1)
        return (pt_ref[b_ * n_pages + jj], 0, 0)

    in_specs = [
        pl.BlockSpec((1, h, d), lambda b_, j, pt, ln, us: (b_, 0, 0)),
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
        _paged_body,
        page=page,
        n_pages=n_pages,
        kvh=kvh,
        rep=rep,
        d=d,
        scale=scale,
        quantized=quantized,
    )
    if not quantized:
        body = functools.partial(_drop_scale_refs, body)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, d), lambda b_, j, pt, ln, us: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        name="paged_decode",
        interpret=interpret,
    )(flat_table, lengths, used, *args)


def _drop_scale_refs(body, pt, ln, us, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                     acc_ref):
    return body(pt, ln, us, q_ref, k_ref, v_ref, None, None, o_ref, m_ref,
                l_ref, acc_ref)
