"""Per-kernel allclose validation: stream converters vs pure-jnp oracles.

Sweeps shapes/dtypes per the kernel-validation contract and adds
hypothesis property tests on the packing invariants.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

DTYPES = [jnp.float32, jnp.bfloat16, jnp.int32]
SHAPES = [(16, 128), (64, 128), (64, 256), (40, 384), (128, 512)]


def _rand(rng, shape, dtype):
    x = rng.normal(size=shape)
    if dtype == jnp.int32:
        return jnp.asarray((x * 100).astype(np.int32))
    return jnp.asarray(x, dtype=dtype)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("stride", [1, 2, 3, 5])
def test_strided_gather(shape, dtype, stride):
    rng = np.random.default_rng(0)
    src = _rand(rng, shape, dtype)
    count = max(1, (shape[0] - 1) // stride)
    out = ops.strided_gather(src, 0, stride, count)
    expect = ref.strided_gather(src, 0, stride, count)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize("shape", SHAPES[:3])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("stride", [2, 4])
def test_strided_scatter(shape, dtype, stride):
    rng = np.random.default_rng(1)
    count = (shape[0] - 1) // stride
    packed = _rand(rng, (count, shape[1]), dtype)
    dst = _rand(rng, shape, dtype)
    out = ops.strided_scatter(dst, packed, 1, stride)
    expect = ref.strided_scatter(dst, packed, 1, stride)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("count", [1, 8, 23, 64])
def test_indirect_gather(shape, dtype, count):
    rng = np.random.default_rng(2)
    src = _rand(rng, shape, dtype)
    idx = jnp.asarray(rng.integers(0, shape[0], count), dtype=jnp.int32)
    out = ops.indirect_gather(src, idx)
    expect = ref.indirect_gather(src, idx)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize("shape", SHAPES[:3])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("count", [8, 13])
def test_indirect_scatter_unique(shape, dtype, count):
    rng = np.random.default_rng(3)
    packed = _rand(rng, (count, shape[1]), dtype)
    dst = _rand(rng, shape, dtype)
    idx = jnp.asarray(rng.permutation(shape[0])[:count], dtype=jnp.int32)
    out = ops.indirect_scatter(dst, packed, idx)
    expect = ref.indirect_scatter(dst, packed, idx)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_indirect_scatter_preserves_untouched():
    dst = jnp.full((32, 128), 7.0)
    packed = jnp.zeros((4, 128))
    idx = jnp.asarray([1, 2, 3, 4], dtype=jnp.int32)
    out = ops.indirect_scatter(dst, packed, idx)
    assert np.allclose(np.asarray(out)[0], 7.0)
    assert np.allclose(np.asarray(out)[5:], 7.0)


@settings(max_examples=25, deadline=None)
@given(
    n_rows=st.integers(8, 64),
    count=st.integers(1, 32),
    seed=st.integers(0, 2**31 - 1),
)
def test_gather_scatter_roundtrip(n_rows, count, seed):
    """Property: scatter(gather(x, idx), idx) restores x at idx (unique idx)."""
    rng = np.random.default_rng(seed)
    count = min(count, n_rows)
    src = jnp.asarray(rng.normal(size=(n_rows, 128)), dtype=jnp.float32)
    idx = jnp.asarray(rng.permutation(n_rows)[:count], dtype=jnp.int32)
    packed = ops.indirect_gather(src, idx)
    restored = ops.indirect_scatter(jnp.zeros_like(src), packed, idx)
    np.testing.assert_allclose(
        np.asarray(restored)[np.asarray(idx)], np.asarray(src)[np.asarray(idx)]
    )


@settings(max_examples=25, deadline=None)
@given(
    stride=st.integers(2, 8),
    count=st.integers(1, 16),
    seed=st.integers(0, 2**31 - 1),
)
def test_strided_equals_indirect_with_arange(stride, count, seed):
    """Property: a strided stream ≡ an indirect stream with arange indices."""
    rng = np.random.default_rng(seed)
    n = stride * count + 1
    src = jnp.asarray(rng.normal(size=(n, 128)), dtype=jnp.float32)
    a = ops.strided_gather(src, 0, stride, count)
    b = ops.indirect_gather(src, jnp.arange(count, dtype=jnp.int32) * stride)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# Row shapes the serving path streams, in the dtypes it streams them in: K/V
# token rows (KVH, hd) and pages (page, KVH, hd), int8 scale rows (KVH,),
# flat rows, and odd rows that need a padded DMA view.
ROWS = [(4, 128), (8, 4, 128), (512,), (4,), (2, 32), (3, 5)]
ROW_DTYPES = [jnp.float32, jnp.bfloat16, jnp.int8]


def _rows(rng, n, row, dtype):
    x = rng.normal(size=(n,) + row) * 50
    if dtype == jnp.int8:
        return jnp.asarray(np.clip(x, -127, 127).astype(np.int8))
    return jnp.asarray(x, dtype=dtype)


@pytest.mark.parametrize("row", ROWS)
@pytest.mark.parametrize("dtype", ROW_DTYPES)
@pytest.mark.parametrize("op", ["strided_gather", "strided_scatter",
                                "indirect_gather", "indirect_scatter"])
def test_converters_match_ref_on_narrow_and_multidim_rows(op, dtype, row):
    """Every converter equals its oracle bit for bit on bf16 and int8 rows
    and on rows of any rank; scatters keep untouched rows, drop targets
    out of range, and resolve duplicate targets last-writer-wins."""
    rng = np.random.default_rng(4)
    n, count = 37, 11
    src = _rows(rng, n, row, dtype)
    packed = _rows(rng, count, row, dtype)
    if op == "strided_gather":
        out = ops.strided_gather(src, 2, 3, count)
        expect = ref.strided_gather(src, 2, 3, count)
    elif op == "strided_scatter":
        # stride 4 from base 1 runs rows 1..41: the last rows fall off.
        out = ops.strided_scatter(src, packed, 1, 4)
        expect = ref.strided_scatter(src, packed, 1, 4)
    elif op == "indirect_gather":
        idx = jnp.asarray(rng.integers(0, n, count), jnp.int32)
        out = ops.indirect_gather(src, idx)
        expect = ref.indirect_gather(src, idx)
    else:
        idx = jnp.asarray([5, 0, 36, 5, n, 9, 9, 9, 1, n + 3, 5], jnp.int32)
        out = ops.indirect_scatter(src, packed, idx)
        expect = ref.indirect_scatter(src, packed, idx)
        got = np.asarray(out)
        np.testing.assert_array_equal(got[5], np.asarray(packed)[10])
        np.testing.assert_array_equal(got[9], np.asarray(packed)[7])
        np.testing.assert_array_equal(got[2], np.asarray(src)[2])
    assert out.shape == expect.shape and out.dtype == expect.dtype
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize("op", ["strided_scatter", "indirect_scatter"])
def test_scatter_casts_rows_to_destination_dtype(op):
    """fp32 rows written into a bf16 pool land rounded, as XLA's scatter
    rounds them (the served model computes K/V in fp32 over bf16 pools)."""
    rng = np.random.default_rng(5)
    dst = _rows(rng, 16, (4, 128), jnp.bfloat16)
    packed = _rows(rng, 3, (4, 128), jnp.float32)
    if op == "strided_scatter":
        out = ops.strided_scatter(dst, packed, 1, 5)
        expect = ref.strided_scatter(dst, packed.astype(dst.dtype), 1, 5)
    else:
        idx = jnp.asarray([3, 11, 7], jnp.int32)
        out = ops.indirect_scatter(dst, packed, idx)
        expect = ref.indirect_scatter(dst, packed.astype(dst.dtype), idx)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))
