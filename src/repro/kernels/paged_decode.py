"""Paged-KV decode attention kernel — the flagship indirect-stream application.

A paged KV cache stores sequences as scattered fixed-size physical pages; the
page table is exactly an AXI-Pack *indirect stream descriptor*: a memory-
resident index array resolved near memory.  Here the page table rides the
scalar-prefetch channel into SMEM, and the kernel turns each live entry into
a direct HBM→VMEM page DMA: ``ppb`` pages of a sequence land side by side in
one VMEM block, so the core sees a dense ``(ppb · page)``-token burst and
never touches an address computation (the paper's element request
generator, with bursts of several pages).

Supports an int8-quantized KV pool (per-(page-token, kv-head) scales): the
TPU analogue of packing *narrower elements* onto the bus — a quarter of the
fp32 HBM traffic (half of bf16) for the bandwidth-bound decode step,
exactly the paper's element-size argument in §III-E.  The scale pages ride
the same page ids as their K/V pages into a block of their own; the write
side (``ops.paged_kv_append`` / ``ops.paged_kv_write_chunk`` with scale
pools) quantizes on write through the same indices.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: VMEM for one walk's double-buffered K/V (and scale) blocks, under v5e's
#: 16 MiB scoped default.
VMEM_BUDGET = 8 << 20
#: Most pages one block gathers.
MAX_PAGES_PER_BLOCK = 16

_ANY = pl.BlockSpec(memory_space=pl.ANY)


def _vmem_tile_bytes(rows: int, cols: int, dtype) -> int:
    """VMEM bytes of a ``(rows, cols)`` minor tile once padded to the
    dtype's native ``(8 · 4/itemsize, 128)`` tiling."""
    item = jnp.dtype(dtype).itemsize
    sub = 8 * max(1, 4 // item)
    return -(-rows // sub) * sub * (-(-cols // 128) * 128) * item


def pages_per_block(page: int, kvh: int, d: int, kv_dtype, n_pages: int,
                    quantized: bool = False) -> int:
    """Pages one step of the decode walk gathers: the largest power of two
    up to ``MAX_PAGES_PER_BLOCK`` and ``n_pages`` whose two K+V(+scale)
    blocks fit ``VMEM_BUDGET`` (a bf16 ``(4, 128)`` token tile pads to
    ``(16, 128)`` there)."""
    per_page = page * _vmem_tile_bytes(kvh, d, kv_dtype)
    if quantized:
        per_page += _vmem_tile_bytes(page, kvh, jnp.float32)
    per_page *= 2 * 2                      # K and V, two buffers each
    ppb = 1
    while (2 * ppb <= min(MAX_PAGES_PER_BLOCK, n_pages)
           and 2 * ppb * per_page <= VMEM_BUDGET):
        ppb *= 2
    return ppb


def _walk_body(
    # scalar prefetch
    page_table_ref,   # (B * n_pages,) physical page ids
    lengths_ref,      # (B,) current KV length per sequence
    used_ref,         # (B,) mapped-page count per sequence (ceil(len/page))
    # inputs
    q_ref,            # (1, H, D)
    k_hbm,            # (P, page, KVH, D), left in HBM
    v_hbm,
    k_scale_hbm,      # (P, page, KVH) or None
    v_scale_hbm,
    # output
    o_ref,            # (1, H, D)
    # scratch
    k_buf,            # (2, ppb, page, KVH, D)
    v_buf,
    k_scale_buf,      # (2, ppb, page, lanes) or None
    v_scale_buf,
    sems,             # DMA (2,): one per buffer
    *,
    ppb: int,
    page: int,
    n_pages: int,
    kvh: int,
    rep: int,
    d: int,
    scale: float,
    lanes: int,
):
    b = pl.program_id(0)
    seq_len = lengths_ref[b]
    used = used_ref[b]
    n_blocks = (used + ppb - 1) // ppb
    tokens = ppb * page
    # (pool in HBM, its block buffer, the page's index past its page id)
    pools = [(k_hbm, k_buf, ()), (v_hbm, v_buf, ())]
    quantized = k_scale_hbm is not None
    if quantized:
        rows = (slice(None), pl.ds(0, lanes))
        pools += [(k_scale_hbm, k_scale_buf, rows),
                  (v_scale_hbm, v_scale_buf, rows)]

    # Pages past a row's last are never copied, so a block's tail holds
    # whatever the buffer held before; it is masked out below, and zeroed
    # once here so that it is at least finite (0 · NaN would leak into the
    # output).  The walk is sequential, so step 0 runs first.
    @pl.when(b == 0)
    def _zero():
        for _, buf, _ in pools:
            buf[...] = jnp.zeros_like(buf)

    def block_copies(blk, slot, op):
        """``op`` on the page DMAs of block ``blk`` into buffer ``slot``:
        only the row's mapped pages are copied."""
        for p in range(ppb):
            j = blk * ppb + p

            @pl.when(j < used)
            def _():
                pid = page_table_ref[b * n_pages + j]
                for hbm, buf, rows in pools:
                    op(pltpu.make_async_copy(hbm.at[(pid,) + rows],
                                             buf.at[slot, p], sems.at[slot]))

    @pl.when(n_blocks > 0)
    def _first():
        block_copies(0, 0, lambda c: c.start())

    q = q_ref[0].astype(jnp.float32)
    heads = _HeadRows(kvh, d, k_buf.dtype, tokens)
    pos = heads.positions()                              # (1, tokens)

    def step(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_blocks)
        def _prefetch():
            block_copies(i + 1, 1 - slot, lambda c: c.start())

        block_copies(i, slot, lambda c: c.wait())
        mask = i * tokens + pos < seq_len
        ks = heads.split(k_buf.at[slot])
        vs = heads.split(v_buf.at[slot])
        if quantized:
            ks = heads.scale(ks, k_scale_buf.at[slot])
            vs = heads.scale(vs, v_scale_buf.at[slot])
        out = []
        for g in range(kvh):
            m, l, acc = carry[g]
            # scores: (rep, tokens)
            s = jax.lax.dot_general(
                q[g * rep:(g + 1) * rep], ks[g], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
            pv = jnp.dot(p, vs[g], preferred_element_type=jnp.float32)
            out.append((m_new, l, acc * alpha + pv))
        return tuple(out)

    init = tuple((jnp.full((rep, 1), NEG_INF, jnp.float32),
                  jnp.zeros((rep, 1), jnp.float32),
                  jnp.zeros((rep, d), jnp.float32)) for _ in range(kvh))
    carry = jax.lax.fori_loop(0, n_blocks, step, init)
    for g, (_, l, acc) in enumerate(carry):
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, g * rep:(g + 1) * rep, :] = (acc / l).astype(o_ref.dtype)


class _HeadRows:
    """Per-KV-head ``(tokens, D)`` float32 rows of a ``(ppb, page, KVH, D)``
    VMEM block, read through 32-bit words.

    A 32-bit word of the block holds ``packing = 4 / itemsize`` consecutive
    rows of its ``(tokens · KVH, D)`` view.  With ``KVH >= packing`` the
    words of one token are ``KVH / packing`` rows apart, so a strided load
    per word offset gives ``packing`` heads at once, tokens in order.  With
    fewer heads than ``packing`` a word holds ``packing / KVH`` tokens, and
    a head's rows come out token-interleaved: sub-token ``u`` of every word
    first, then ``u + 1``.  :meth:`positions` gives that order, so masks,
    K and V all agree.
    """

    def __init__(self, kvh, d, dtype, tokens):
        self.kvh, self.d, self.tokens = kvh, d, tokens
        self.bits = jnp.dtype(dtype).itemsize * 8
        self.packing = 32 // self.bits
        self.float_dtype = jnp.issubdtype(dtype, jnp.floating)
        self.stride = max(1, kvh // self.packing)       # words per token
        self.tpw = max(1, self.packing // kvh)          # tokens per word
        self.n = tokens // self.tpw                     # rows per load

    def positions(self):
        """(1, tokens) position in the block of each row a head reads."""
        r = jax.lax.broadcasted_iota(jnp.int32, (1, self.tokens), 1)
        return (r % self.n) * self.tpw + r // self.n

    def _field(self, word, s):
        """Element ``s`` of each 32-bit word, as float32."""
        if self.packing == 1:
            return word.astype(jnp.float32)
        w = pltpu.bitcast(word, jnp.int32)
        if self.float_dtype:                  # bf16: the bits of a float32
            hi = w << (32 - self.bits * (s + 1))
            return pltpu.bitcast(hi & jnp.int32(-(1 << (32 - self.bits))),
                                 jnp.float32)
        # signed integer codes: shift to the top, then arithmetically down
        return ((w << (32 - self.bits * (s + 1))) >> (32 - self.bits)
                ).astype(jnp.float32)

    def split(self, buf):
        """[(tokens, D) float32 rows of head g for g in range(KVH)]."""
        rows = buf.reshape(self.tokens * self.kvh, self.d)
        if self.packing > 1:
            rows = rows.bitcast(jnp.int32)
        pieces = {}
        for w in range(self.stride):
            word = rows[pl.ds(w, self.n, stride=self.stride), :]
            for s in range(self.packing):
                r = w * self.packing + s
                pieces.setdefault(r % self.kvh, []).append(
                    self._field(word, s))
        return [p[0] if len(p) == 1 else jnp.concatenate(p, axis=0)
                for _, p in sorted(pieces.items())]

    def scale(self, heads, scale_buf):
        """Multiply each head's rows by its per-token scales (lane ``g``
        of a ``(ppb, page, lanes)`` scale block)."""
        sc = scale_buf.reshape(self.tokens, scale_buf.shape[-1])
        out = []
        for g, x in enumerate(heads):
            cols = [sc[pl.ds(u, self.n, stride=self.tpw), g:g + 1]
                    for u in range(self.tpw)]
            col = cols[0] if len(cols) == 1 else jnp.concatenate(cols, 0)
            out.append(x * col)
        return out


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

    The page walk is bounded by each sequence's own pages: the grid is
    ``(B,)``, and step ``b`` loops over ``ceil(used[b] / ppb)`` blocks of
    ``ppb`` pages (:func:`pages_per_block`), where ``used[b] =
    ceil(len[b] / page)`` rides the scalar-prefetch channel beside the table.
    Each block's page DMAs (K, V and, for int8 pools, their scales) go into
    one of two VMEM buffers, and block ``i + 1``'s copies start before
    block ``i`` is computed.  Only mapped pages are copied: an empty sequence copies
    nothing and outputs exact zeros, and short sequences in a long-table
    batch stream only what they own.  The online softmax runs once per
    block over its ``ppb · page`` tokens, masked by the length.  The pools
    stay in HBM (``pl.ANY``), and the walk is ``arbitrary`` (sequential).
    """
    b, h, d = q.shape
    _, page, kvh, _ = k_pages.shape
    n_pages = page_table.shape[1]
    rep = h // kvh
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    quantized = k_scale is not None
    ppb = pages_per_block(page, kvh, d, k_pages.dtype, n_pages, quantized)
    # A scale page (page, KVH) lies in HBM one token per 128-lane row, and
    # Mosaic slices it only whole rows wide; the interpreter knows no
    # padding.  Lanes past KVH are copied and never read.
    lanes = kvh if interpret else -(-kvh // 128) * 128

    flat_table = page_table.reshape(-1).astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    used = (-(-lengths // page)).astype(jnp.int32)

    row = pl.BlockSpec((1, h, d), lambda b_, pt, ln, us: (b_, 0, 0))
    in_specs = [row, _ANY, _ANY]
    args = [q, k_pages, v_pages]
    scratch = [pltpu.VMEM((2, ppb, page, kvh, d), k_pages.dtype),
               pltpu.VMEM((2, ppb, page, kvh, d), v_pages.dtype)]
    if quantized:
        in_specs += [_ANY, _ANY]
        args += [k_scale, v_scale]
        scratch += [pltpu.VMEM((2, ppb, page, lanes), k_scale.dtype),
                    pltpu.VMEM((2, ppb, page, lanes), v_scale.dtype)]
    scratch.append(pltpu.SemaphoreType.DMA((2,)))

    body = functools.partial(
        _walk_body, ppb=ppb, page=page, n_pages=n_pages, kvh=kvh, rep=rep,
        d=d, scale=scale, lanes=lanes,
    )
    if not quantized:
        body = functools.partial(_drop_scale_refs, body)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=in_specs,
        out_specs=row,
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        name="paged_decode",
        interpret=interpret,
    )(flat_table, lengths, used, *args)


def _drop_scale_refs(body, pt, ln, us, q_ref, k_hbm, v_hbm, o_ref, k_buf,
                     v_buf, sems):
    return body(pt, ln, us, q_ref, k_hbm, v_hbm, None, None, o_ref, k_buf,
                v_buf, None, None, sems)
