"""Unified differential-oracle sweep: ref ≡ pallas across the cross-product.

One seeded, parametrized suite replacing the per-kernel ad-hoc ref≡pallas
cases (the GQA/int8 spot checks that used to live in test_paged_prefill.py
and test_workload_kernels.py): both paged kernels × {fp32, int8} (and bf16
pools for decode) × {GQA group 1/2/4} × ragged / page-boundary length
patterns, and for decode the edges of its multi-page block walk.  A future
kernel edit gets the full cross-product for free — a new length pattern or
GQA shape added below lands on every kernel and dtype at once.

Each case's RNG is seeded from its parameter id, so failures name the exact
cell and reproduce run-to-run; specials that don't fit a cross-product
(poison-page DMA clamps, bf16 accumulation, padding-row NaN guards) stay
with their kernel's dedicated test module.
"""
import zlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.paged_decode import pages_per_block

PAGE = 4
POOL = 24

#: GQA group size g = h / kvh — the grouping the kernels resolve per KV head.
GQA = {1: (4, 4), 2: (4, 2), 4: (8, 2)}

#: Decode length patterns over a 4-page table (max 16 tokens): ragged
#: mid-page lengths, exact page multiples (the off-by-one spot for the page
#: walk), inactive rows (length 0 — what a masked decode slot passes), a
#: single live token, and the completely full table.
DECODE_LENGTHS = {
    "ragged": [1, 7, 14],
    "page_multiple": [4, 8, 16],
    "with_inactive": [0, 5, 9],
    "minimal": [1, 1, 1],
    "full": [16, 16, 16],
}

#: Block-walk patterns: the decode kernel gathers ``ppb`` pages per block
#: (:func:`pages_per_block`, 16 here), and these table widths are not a
#: multiple of it.  Their rows are empty, one token, one page, one block
#: less, exactly and one more than a block (``ppb * PAGE``), and the full
#: table.
DECODE_WALKS = {"walk37": 37, "walk40": 40}


def _walk_lengths(npg, ppb):
    block = ppb * PAGE
    return [0, 1, PAGE, block - 1, block, block + 1, npg * PAGE]

#: Prefill (starts, counts) patterns with a chunk width of 8: ragged
#: mid-page starts, chunks straddling page boundaries, start+count landing
#: exactly on page boundaries, and padding rows (count 0, including a
#: degenerate non-zero start).
PREFILL_CHUNKS = {
    "ragged": ([0, 6, 3], [8, 8, 5]),
    "straddle": ([2, 7, 5], [6, 5, 3]),
    "page_multiple": ([0, 4, 12], [4, 4, 4]),
    "padding_rows": ([0, 12, 9], [8, 0, 0]),
}


def _seed(*parts) -> int:
    return zlib.crc32("/".join(str(p) for p in parts).encode())


def _pool(rng, kvh, d, quantize, dtype=jnp.float32, pages=POOL):
    kp = jnp.asarray(rng.normal(size=(pages, PAGE, kvh, d)), dtype)
    vp = jnp.asarray(rng.normal(size=(pages, PAGE, kvh, d)), dtype)
    if not quantize:
        return kp, vp, {}
    kq, ks = ref.quantize_kv(kp)
    vq, vs = ref.quantize_kv(vp)
    return kq, vq, dict(k_scale=ks, v_scale=vs)


@pytest.mark.parametrize("lengths_name",
                         sorted(DECODE_LENGTHS) + sorted(DECODE_WALKS))
@pytest.mark.parametrize("group", sorted(GQA))
@pytest.mark.parametrize("dtype", ["fp32", "int8", "bf16"])
def test_paged_decode_matches_ref(dtype, group, lengths_name):
    h, kvh = GQA[group]
    d = 16
    quantize = dtype == "int8"
    pool_dtype = jnp.bfloat16 if dtype == "bf16" else jnp.float32
    npg = DECODE_WALKS.get(lengths_name, 4)
    ppb = pages_per_block(PAGE, kvh, d, jnp.int8 if quantize else pool_dtype,
                          npg, quantize)
    if lengths_name in DECODE_WALKS:
        assert 1 < ppb < npg and npg % ppb
        lengths = _walk_lengths(npg, ppb)
    else:
        lengths = DECODE_LENGTHS[lengths_name]
    b = len(lengths)
    pages = max(POOL, b * npg)
    rng = np.random.default_rng(_seed("decode", dtype, group, lengths_name))
    kp, vp, scales = _pool(rng, kvh, d, quantize, pool_dtype, pages)
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    pt = jnp.asarray(
        rng.permutation(pages)[: b * npg].reshape(b, npg), jnp.int32
    )
    ln = jnp.asarray(lengths, jnp.int32)
    got = ops.paged_decode_attention(q, kp, vp, pt, ln, impl="pallas", **scales)
    want = ops.paged_decode_attention(q, kp, vp, pt, ln, impl="ref", **scales)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )
    assert np.isfinite(np.asarray(got)).all()
    for i, n in enumerate(lengths):  # inactive rows must stay exact zeros
        if n == 0:
            assert np.abs(np.asarray(got)[i]).max() == 0.0


@pytest.mark.parametrize("chunk_name", sorted(PREFILL_CHUNKS))
@pytest.mark.parametrize("group", sorted(GQA))
@pytest.mark.parametrize("dtype", ["fp32", "int8"])
def test_paged_prefill_matches_ref(dtype, group, chunk_name):
    h, kvh = GQA[group]
    d = 16
    starts, counts = PREFILL_CHUNKS[chunk_name]
    r, c, ctx = len(starts), 8, 4
    rng = np.random.default_rng(_seed("prefill", dtype, group, chunk_name))
    kp, vp, scales = _pool(rng, kvh, d, dtype == "int8")
    q = jnp.asarray(rng.normal(size=(r, c, h, d)), jnp.float32)
    rows = jnp.asarray(
        rng.permutation(POOL)[: r * ctx].reshape(r, ctx), jnp.int32
    )
    st = jnp.asarray(starts, jnp.int32)
    ct = jnp.asarray(counts, jnp.int32)
    got = ops.paged_prefill_attention(
        q, kp, vp, rows, st, ct, impl="pallas", **scales
    )
    want = ops.paged_prefill_attention(
        q, kp, vp, rows, st, ct, impl="ref", **scales
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )
    assert np.isfinite(np.asarray(got)).all()
    for i, n in enumerate(counts):  # padding rows must stay exact zeros
        if n == 0:
            assert np.abs(np.asarray(got)[i]).max() == 0.0


def test_int8_decode_quantization_error_bounded():
    """The int8 path tracks the full-precision pool closely (not just its
    own oracle): the end-to-end dequant error stays small, so serving from
    quantized pages is a bandwidth trade, not an accuracy cliff."""
    rng = np.random.default_rng(_seed("decode", "int8", "error"))
    h, kvh, d, npg = 4, 2, 16, 4
    kp = jnp.asarray(rng.normal(size=(POOL, PAGE, kvh, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(POOL, PAGE, kvh, d)), jnp.float32)
    kq, ks = ref.quantize_kv(kp)
    vq, vs = ref.quantize_kv(vp)
    b = 2
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    pt = jnp.asarray(
        rng.permutation(POOL)[: b * npg].reshape(b, npg), jnp.int32
    )
    ln = jnp.asarray([7, 14], jnp.int32)
    out = ops.paged_decode_attention(
        q, kq, vq, pt, ln, k_scale=ks, v_scale=vs, impl="pallas"
    )
    full = ops.paged_decode_attention(q, kp, vp, pt, ln, impl="ref")
    assert np.abs(np.asarray(out) - np.asarray(full)).max() < 0.05
