"""Flash attention forward kernel (training/prefill path).

Streaming softmax over KV blocks with running (m, l, acc) VMEM scratch —
O(S) memory at any sequence length, which is what makes the 32k prefill and
500k decode shapes lowerable.  Supports causal masking, gemma3-style sliding
windows (a *strided/banded* access pattern: each query block touches only a
window-limited band of KV blocks, skipped entirely via ``pl.when`` when out
of range) and GQA (KV head selected by the BlockSpec ``index_map`` — the
group mapping never materializes repeated KV in HBM).
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


def _flash_body(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    causal: bool,
    window: Optional[int],
    bq: int,
    bk: int,
    scale: float,
    num_kv_blocks: int,
    kv_len: int,
):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    first_q = i * bq
    last_q = first_q + bq - 1
    first_k = j * bk
    last_k = first_k + bk - 1

    visible = jnp.bool_(True)
    if causal:
        visible &= last_q >= first_k
    if window is not None:
        visible &= first_q - last_k < window

    @pl.when(visible)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                          # (bq, bk)
        qi = first_q + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kj = first_k + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kj < kv_len
        if causal:
            mask &= qi >= kj
        if window is not None:
            mask &= qi - kj < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                              # (bq, 1)
        m_blk = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)                    # (bq, 1)
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == num_kv_blocks - 1)
    def _finalize():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _flash_body_lse(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, **kw):
    """Forward body that additionally emits log-sum-exp rows (for backward)."""
    _flash_body(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, **kw)
    j = pl.program_id(3)

    @pl.when(j == kw["num_kv_blocks"] - 1)
    def _emit():
        l = jnp.where(l_ref[:, :1] == 0.0, 1.0, l_ref[:, :1])
        lse_ref[0, 0] = (m_ref[:, :1] + jnp.log(l))[:, 0]


def flash_attention_kernel(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Forward flash attention. q (B,H,Sq,D); k,v (B,KVH,Skv,D) → (B,H,Sq,D)."""
    b, h, sq, d = q.shape
    _, kvh, skv, _ = k.shape
    assert h % kvh == 0
    rep = h // kvh
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    assert sq % bq == 0 and skv % bk == 0, "wrapper must pad seq lens"
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    grid = (b, h, sq // bq, skv // bk)
    body = functools.partial(
        _flash_body,
        causal=causal,
        window=window,
        bq=bq,
        bk=bk,
        scale=scale,
        num_kv_blocks=skv // bk,
        kv_len=skv,
    )
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, i, j: (b_, h_ // rep, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, i, j: (b_, h_ // rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)


def flash_attention_fwd_kernel(
    q, k, v, causal=True, window=None, scale=None,
    block_q=128, block_k=128, interpret=False,
):
    """Forward returning (o, lse) — the residuals the backward kernels need."""
    b, h, sq, d = q.shape
    _, kvh, skv, _ = k.shape
    rep = h // kvh
    bq, bk = min(block_q, sq), min(block_k, skv)
    assert sq % bq == 0 and skv % bk == 0
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    body = functools.partial(
        _flash_body_lse, causal=causal, window=window, bq=bq, bk=bk,
        scale=scale, num_kv_blocks=skv // bk, kv_len=skv,
    )
    return pl.pallas_call(
        body,
        grid=(b, h, sq // bq, skv // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, i, j: (b_, h_ // rep, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, i, j: (b_, h_ // rep, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b_, h_, i, j: (b_, h_, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        name="flash_attention_fwd",
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Backward kernels (FlashAttention-2 style, two passes)
# ---------------------------------------------------------------------------


def _mask_block(first_q, first_k, bq, bk, causal, window, window_flag=None):
    qi = first_q + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kj = first_k + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask &= qi >= kj
    if window is not None:
        mask &= qi - kj < window
    return mask


def _dkv_body(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc, *, causal, window, bq, bk, scale, num_q_blocks, rep,
):
    # grid (B, KVH, Skv/bk, rep, Sq/bq): dk/dv accumulate over (rep, i)
    j = pl.program_id(2)
    r = pl.program_id(3)
    i = pl.program_id(4)

    @pl.when((r == 0) & (i == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    first_q, first_k = i * bq, j * bk
    visible = jnp.bool_(True)
    if causal:
        visible &= first_q + bq - 1 >= first_k
    if window is not None:
        visible &= first_q - (first_k + bk - 1) < window

    @pl.when(visible)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0].astype(jnp.float32)          # (bq,)
        delta = delta_ref[0, 0].astype(jnp.float32)      # (bq,)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        mask = _mask_block(first_q, first_k, bq, bk, causal, window)
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)   # (bq, bk)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None]) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when((r == rep - 1) & (i == num_q_blocks - 1))
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _dq_body(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
    *, causal, window, bq, bk, scale, num_kv_blocks,
):
    # grid (B, H, Sq/bq, Skv/bk): dq accumulates over j
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    first_q, first_k = i * bq, j * bk
    visible = jnp.bool_(True)
    if causal:
        visible &= first_q + bq - 1 >= first_k
    if window is not None:
        visible &= first_q - (first_k + bk - 1) < window

    @pl.when(visible)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0].astype(jnp.float32)
        delta = delta_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        mask = _mask_block(first_q, first_k, bq, bk, causal, window)
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None]) * scale
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(j == num_kv_blocks - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def flash_attention_bwd_kernel(
    q, k, v, o, lse, do, causal=True, window=None, scale=None,
    block_q=128, block_k=128, interpret=False,
):
    """FlashAttention-2-style backward: returns (dq, dk, dv).

    dk/dv kernel: grid (B, KVH, Skv/bk, rep, Sq/bq) — each kv-head block
    accumulates over its GQA group and all q blocks (recomputing p from the
    saved lse, never materializing (Sq, Skv)).  dq kernel: grid
    (B, H, Sq/bq, Skv/bk).  delta = rowsum(do·o) precomputed outside.
    """
    b, h, sq, d = q.shape
    _, kvh, skv, _ = k.shape
    rep = h // kvh
    bq, bk = min(block_q, sq), min(block_k, skv)
    assert sq % bq == 0 and skv % bk == 0
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    dkv_body = functools.partial(
        _dkv_body, causal=causal, window=window, bq=bq, bk=bk,
        scale=scale, num_q_blocks=sq // bq, rep=rep,
    )
    # q/do/lse/delta blocks walk the GQA group: head = kvh_idx * rep + r
    q_map = lambda b_, g, j, r, i: (b_, g * rep + r, i, 0)
    v_map = lambda b_, g, j, r, i: (b_, g, j, 0)
    s_map = lambda b_, g, j, r, i: (b_, g * rep + r, i)
    dk, dv = pl.pallas_call(
        dkv_body,
        grid=(b, kvh, skv // bk, rep, sq // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), q_map),
            pl.BlockSpec((1, 1, bk, d), v_map),
            pl.BlockSpec((1, 1, bk, d), v_map),
            pl.BlockSpec((1, 1, bq, d), q_map),
            pl.BlockSpec((1, 1, bq), s_map),
            pl.BlockSpec((1, 1, bq), s_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda b_, g, j, r, i: (b_, g, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, g, j, r, i: (b_, g, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        name="flash_attention_bwd_dkv",
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dq_body = functools.partial(
        _dq_body, causal=causal, window=window, bq=bq, bk=bk,
        scale=scale, num_kv_blocks=skv // bk,
    )
    dq = pl.pallas_call(
        dq_body,
        grid=(b, h, sq // bq, skv // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, i, j: (b_, h_ // rep, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, i, j: (b_, h_ // rep, j, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b_, h_, i, j: (b_, h_, i)),
            pl.BlockSpec((1, 1, bq), lambda b_, h_, i, j: (b_, h_, i)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        name="flash_attention_bwd_dq",
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv
