"""Int8 KV page pools, end to end.

Quantize-on-write through both write ops (codes + per-(page-token, kv-head)
scales through the same indirect burst), both attention kernels reading the
quantized pool (in-VMEM dequant vs the shared ``dequantize_pages`` oracle
rule), the engine/scheduler serving mode (``kv_dtype='int8'``: donated
scale pools, eviction/replay rebuilding codes *and* scales bit-for-bit).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.kernels import ops, ref
from repro.serve import (
    PagedKVCache,
    PagedLM,
    Request,
    Scheduler,
    static_batch_generate,
)

CFG = smoke_config("yi-6b")

# Quantization tolerance: int8 symmetric per-(token, kv-head) rounding on
# unit-normal KV rows; attention outputs are convex combinations of V rows,
# so the error stays at the per-element quant noise level.
QTOL = dict(rtol=0.0, atol=0.12)


def _int8_pool(pool, page, kvh, d):
    kp = jnp.zeros((pool, page, kvh, d), jnp.int8)
    vp = jnp.zeros((pool, page, kvh, d), jnp.int8)
    ks = jnp.ones((pool, page, kvh), jnp.float32)
    vs = jnp.ones((pool, page, kvh), jnp.float32)
    return kp, vp, ks, vs


def _models(kv_dtype=None):
    return (
        PagedLM(CFG, jax.random.PRNGKey(0), impl="ref", kv_dtype=kv_dtype),
        PagedLM(CFG, jax.random.PRNGKey(0), impl="pallas", kv_dtype=kv_dtype),
    )


def _prompts(rng, lens):
    return [rng.integers(0, CFG.vocab, n).astype(np.int32) for n in lens]


# ---------------------------------------------------------------------------
# Quantize-on-write round trips: write int8 → read both kernels → allclose
# to the fp32 oracle within quantization tolerance
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_append_roundtrip_decode_matches_fp32_oracle(impl):
    rng = np.random.default_rng(0)
    pool, page, kvh, d, b, npg, h = 12, 4, 2, 32, 3, 3, 4
    kp8, vp8, ks, vs = _int8_pool(pool, page, kvh, d)
    kpf = jnp.zeros((pool, page, kvh, d), jnp.float32)
    vpf = jnp.zeros((pool, page, kvh, d), jnp.float32)
    table = jnp.asarray(rng.permutation(pool)[: b * npg].reshape(b, npg),
                        jnp.int32)
    lengths = jnp.asarray([0, 3, 7], jnp.int32)
    # Append a few tokens per sequence through the quantizing write.
    for _ in range(4):
        kn = jnp.asarray(rng.normal(size=(b, kvh, d)), jnp.float32)
        vn = jnp.asarray(rng.normal(size=(b, kvh, d)), jnp.float32)
        kp8, vp8, _, ks, vs = ops.paged_kv_append(
            kp8, vp8, kn, vn, table, lengths, k_scale=ks, v_scale=vs,
            impl=impl,
        )
        kpf, vpf, lengths = ops.paged_kv_append(
            kpf, vpf, kn, vn, table, lengths, impl="ref"
        )
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    got = ops.paged_decode_attention(
        q, kp8, vp8, table, lengths, k_scale=ks, v_scale=vs, impl=impl
    )
    want = ops.paged_decode_attention(q, kpf, vpf, table, lengths, impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **QTOL)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_chunk_write_roundtrip_prefill_matches_fp32_oracle(impl):
    """Chunked writes straddling page boundaries, then chunk attention from
    the quantized pool, vs the full-precision write + oracle read."""
    rng = np.random.default_rng(1)
    pool, page, kvh, d, r, npg, c, h = 12, 4, 2, 32, 2, 3, 6, 4
    kp8, vp8, ks, vs = _int8_pool(pool, page, kvh, d)
    kpf = jnp.zeros((pool, page, kvh, d), jnp.float32)
    vpf = jnp.zeros((pool, page, kvh, d), jnp.float32)
    rows = jnp.asarray(rng.permutation(pool)[: r * npg].reshape(r, npg),
                       jnp.int32)
    kn = jnp.asarray(rng.normal(size=(r, c, kvh, d)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(r, c, kvh, d)), jnp.float32)
    st = jnp.asarray([2, 7], jnp.int32)          # both straddle a boundary
    ct = jnp.asarray([6, 5], jnp.int32)
    kp8, vp8, ks, vs = ops.paged_kv_write_chunk(
        kp8, vp8, kn, vn, rows, st, ct, k_scale=ks, v_scale=vs, impl=impl
    )
    kpf, vpf = ops.paged_kv_write_chunk(kpf, vpf, kn, vn, rows, st, ct,
                                        impl="ref")
    q = jnp.asarray(rng.normal(size=(r, c, h, d)), jnp.float32)
    got = ops.paged_prefill_attention(
        q, kp8, vp8, rows, st, ct, k_scale=ks, v_scale=vs, impl=impl
    )
    want = ops.paged_prefill_attention(q, kpf, vpf, rows, st, ct, impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **QTOL)


def test_quantized_write_ops_pallas_bitwise_matches_ref():
    """The converter-kernel write path produces the identical int8 codes and
    scales as the oracle scatter — quantization happens once, before the
    stream, so the two paths can be compared bitwise."""
    rng = np.random.default_rng(2)
    pool, page, kvh, d, r, npg, c = 10, 4, 2, 16, 3, 2, 5
    kp8, vp8, ks, vs = _int8_pool(pool, page, kvh, d)
    rows = jnp.asarray(rng.permutation(pool)[: r * npg].reshape(r, npg),
                       jnp.int32)
    kn = jnp.asarray(rng.normal(size=(r, c, kvh, d)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(r, c, kvh, d)), jnp.float32)
    st = jnp.asarray([0, 3, 6], jnp.int32)
    ct = jnp.asarray([5, 0, 2], jnp.int32)       # incl. a padding row
    outs = [
        ops.paged_kv_write_chunk(kp8, vp8, kn, vn, rows, st, ct,
                                 k_scale=ks, v_scale=vs, impl=im)
        for im in ("ref", "pallas")
    ]
    for a, b in zip(*outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("quantized", [False, True])
def test_append_past_table_row_drops_like_oracle(quantized):
    """A sequence whose length already fills its table row must append
    *nothing* under both implementations (the oracle's ``mode='drop'``) —
    regression for the converter path clamping the un-mapped slot gather
    onto physical page 0 and clobbering it."""
    rng = np.random.default_rng(8)
    pool, page, kvh, d = 6, 4, 1, 8
    kp = jnp.asarray(rng.integers(-5, 5, (pool, page, kvh, d)),
                     jnp.int8 if quantized else jnp.float32)
    vp = jnp.asarray(rng.integers(-5, 5, (pool, page, kvh, d)), kp.dtype)
    scales = (dict(k_scale=jnp.ones((pool, page, kvh), jnp.float32),
                   v_scale=jnp.ones((pool, page, kvh), jnp.float32))
              if quantized else {})
    kn = jnp.asarray(rng.normal(size=(1, kvh, d)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(1, kvh, d)), jnp.float32)
    table = jnp.asarray([[3, 5]], jnp.int32)
    full = jnp.asarray([8], jnp.int32)           # row capacity: 2 × 4
    for impl in ("ref", "pallas"):
        out = ops.paged_kv_append(kp, vp, kn, vn, table, full,
                                  impl=impl, **scales)
        np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(kp))
        np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(vp))


def test_counts_zero_rows_exact_zero_under_int8():
    """Padding rows must output exact zeros from a quantized pool too — the
    mask logic is upstream of the dequant, under both implementations."""
    rng = np.random.default_rng(3)
    pool, page, kvh, d, r, npg, c, h = 8, 4, 2, 16, 3, 2, 4, 4
    kp8, vp8, ks, vs = _int8_pool(pool, page, kvh, d)
    # Fill the pool with junk codes/scales: a padding row must still be 0.
    kp8 = jnp.asarray(rng.integers(-127, 128, kp8.shape), jnp.int8)
    vp8 = jnp.asarray(rng.integers(-127, 128, vp8.shape), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.01, 2.0, ks.shape), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.01, 2.0, vs.shape), jnp.float32)
    rows = jnp.asarray(rng.permutation(pool)[: r * npg].reshape(r, npg),
                       jnp.int32)
    q = jnp.asarray(rng.normal(size=(r, c, h, d)), jnp.float32)
    st = jnp.asarray([0, 5, 0], jnp.int32)       # incl. degenerate start
    ct = jnp.asarray([4, 0, 0], jnp.int32)
    for impl in ("ref", "pallas"):
        out = np.asarray(ops.paged_prefill_attention(
            q, kp8, vp8, rows, st, ct, k_scale=ks, v_scale=vs, impl=impl
        ))
        assert np.isfinite(out).all()
        assert np.abs(out[1]).max() == 0.0
        assert np.abs(out[2]).max() == 0.0
    # Decode side: an empty sequence reads zero rows from the junk pool.
    lengths = jnp.asarray([0, 6, 8], jnp.int32)
    qd = jnp.asarray(rng.normal(size=(r, h, d)), jnp.float32)
    for impl in ("ref", "pallas"):
        out = np.asarray(ops.paged_decode_attention(
            qd, kp8, vp8, rows, lengths, k_scale=ks, v_scale=vs, impl=impl
        ))
        assert np.isfinite(out).all()
        assert np.abs(out[0]).max() == 0.0


# ---------------------------------------------------------------------------
# Engine / scheduler: kv_dtype='int8' end to end
# ---------------------------------------------------------------------------


def test_int8_pool_bytes_quartered_vs_fp32_halved_vs_bf16():
    kw = dict(batch=2, max_len=32, page=4)
    c32 = PagedKVCache.create(CFG, kv_dtype="fp32", **kw)
    c16 = PagedKVCache.create(CFG, kv_dtype="bf16", **kw)
    c8 = PagedKVCache.create(CFG, kv_dtype="int8", **kw)
    assert c8.k_pages.dtype == jnp.int8 and c8.quantized
    assert c8.k_pages.nbytes * 4 == c32.k_pages.nbytes
    assert c8.k_pages.nbytes * 2 == c16.k_pages.nbytes
    assert c8.k_scale.shape == c8.k_pages.shape[:-1]
    assert not c32.quantized and c32.k_scale is None


def test_int8_engine_pallas_matches_ref_within_quant_noise():
    """Full engine prefill + decode with impl='pallas' over int8 pools stays
    close to the impl='ref' int8 path (identical quantized writes, kernel
    vs oracle dequant read)."""
    rng = np.random.default_rng(4)
    prompts = _prompts(rng, (6, 9))
    logits, caches = {}, {}
    for model in _models("int8"):
        cache = PagedKVCache.create(CFG, batch=2, max_len=32, page=4,
                                    kv_dtype="int8")
        for i, p in enumerate(prompts):
            cache = cache.allocate(i, cache.pages_for(len(p) + 2))
        toks = np.zeros((2, 4), np.int32)
        toks[0] = prompts[0][:4]
        toks[1] = prompts[1][:4]
        lg, cache = model.prefill_batch(
            toks, np.asarray([4, 4], np.int32), np.asarray([0, 1], np.int32),
            np.asarray([0, 0], np.int32), cache,
        )
        lg, cache = model.decode_step(
            np.asarray([3, 5], np.int32), cache, np.asarray([True, True])
        )
        logits[model.impl], caches[model.impl] = np.asarray(lg), cache
    # Near-identical quantized pools on both paths: layer l>0 inputs differ
    # by the kernel-vs-oracle attention numerics of the layer below, so the
    # scales (and rarely a code, on a rounding knife-edge) can drift by
    # float-epsilon — but never by quantization-step amounts.
    np.testing.assert_allclose(
        np.asarray(caches["pallas"].k_pages, np.float32),
        np.asarray(caches["ref"].k_pages, np.float32), atol=1,
    )
    np.testing.assert_allclose(
        np.asarray(caches["pallas"].k_scale),
        np.asarray(caches["ref"].k_scale), rtol=1e-5,
    )
    np.testing.assert_allclose(logits["pallas"], logits["ref"],
                               rtol=1e-4, atol=1e-3)


def test_int8_scheduler_eviction_replay_rebuilds_scales_bit_for_bit():
    """Scale-pool donation + eviction/replay: a run that evicts and replays
    must produce the same tokens as the int8 static batch, and its final
    live pages/scales must match an eviction-free run — no stale scales
    survive a release/re-admission round trip."""
    rng = np.random.default_rng(5)
    prompts = _prompts(rng, (8, 7))
    max_new = 8
    model, _ = _models("int8")

    want = static_batch_generate(
        model, PagedKVCache.create(CFG, batch=2, max_len=16, page=4,
                                   kv_dtype="int8"),
        prompts, max_new, chunk=4,
    )
    # 6-page pool: both requests peak at 4 pages → mid-decode eviction.
    cache = PagedKVCache.create(CFG, batch=2, max_len=16, page=4,
                                pool_pages=6, kv_dtype="int8")
    sched = Scheduler(model, cache, chunk=4)
    reqs = [Request(rid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        sched.submit(r)
    got = sched.run()
    assert sched.stats.n_evictions >= 1
    assert got == {i: want[i] for i in want}

    # Ample-pool run: no evictions; compare each request's live pages+scales.
    cache2 = PagedKVCache.create(CFG, batch=2, max_len=16, page=4,
                                 kv_dtype="int8")
    sched2 = Scheduler(model, cache2, chunk=4)
    for i, p in enumerate(prompts):
        sched2.submit(Request(rid=i, prompt=p, max_new=max_new))
    got2 = sched2.run()
    assert got2 == got
    # Both runs retired everything; every page went back to the free pool
    # and the *content* of the pools for each sequence was identical while
    # live (asserted transitively through the bit-equal token streams above
    # — tokens depend on codes AND scales, so a stale scale would diverge).
    assert sched.cache.n_free == 6


def test_int8_scheduler_matches_pallas_kernels_end_to_end():
    """Continuous batching with impl='pallas' int8 (quantized chunk writes,
    both quantized kernels) reproduces the impl='ref' int8 token stream —
    greedy decode is bit-stable across the kernel/oracle dequant numerics
    on this workload."""
    ref_m, pal_m = _models("int8")
    rng = np.random.default_rng(6)
    prompts = _prompts(rng, (9, 4))
    outs = {}
    for model in (ref_m, pal_m):
        cache = PagedKVCache.create(CFG, batch=2, max_len=32, page=4,
                                    kv_dtype="int8")
        sched = Scheduler(model, cache, chunk=4)
        for i, p in enumerate(prompts):
            sched.submit(Request(rid=i, prompt=p, max_new=5))
        outs[model.impl] = sched.run()
    assert outs["pallas"] == outs["ref"]


def test_scheduler_rejects_mismatched_kv_dtype():
    model, _ = _models("int8")
    cache = PagedKVCache.create(CFG, batch=1, max_len=8, page=4)  # fp32
    with pytest.raises(ValueError):
        Scheduler(model, cache, chunk=4)
    # Width mismatches among float pools are rejected too — a bf16 model
    # over fp32 pools would silently run its KV math at the wrong width.
    model16 = PagedLM(CFG, jax.random.PRNGKey(0), impl="ref",
                      kv_dtype="bf16")
    with pytest.raises(ValueError):
        Scheduler(model16, cache, chunk=4)
    # And create() accepts the model's dtype object directly (the benchmark
    # path), guaranteeing agreement.
    cache16 = PagedKVCache.create(CFG, batch=1, max_len=8, page=4,
                                  kv_dtype=model16.kv_dtype)
    Scheduler(model16, cache16, chunk=4)


# ---------------------------------------------------------------------------
# Layer addressing: each layer's pages inside the one pool
# ---------------------------------------------------------------------------


#: The write and attention ops' keywords for the scale pools, in order.
SCALES = ("k_scale", "v_scale")


def _layerwise_prefill(model, pools, table, tokens, counts, seqs, starts,
                       ctx):
    """The engine's prefill with each layer's pools sliced out and stacked
    back: the addressing its one-pool view replaces."""
    p, impl, (h, kvh, hd) = model.params, model.impl, (model.h, model.kvh,
                                                       model.hd)
    r, c = tokens.shape
    x = jnp.take(p["embed"], tokens, axis=0)
    rows = jnp.take(table, seqs, axis=0)
    new = []
    for l in range(model.cfg.n_layers):
        k, v, *scales = [a[l] for a in pools]
        kn = (x @ p["wk"][l]).reshape(r, c, kvh, hd)
        vn = (x @ p["wv"][l]).reshape(r, c, kvh, hd)
        out = ops.paged_kv_write_chunk(k, v, kn, vn, rows, starts, counts,
                                       impl=impl, **dict(zip(SCALES, scales)))
        new.append(out)
        q = (x @ p["wq"][l]).reshape(r, c, h, hd)
        attn = ops.paged_prefill_attention(q, out[0], out[1], rows[:, :ctx],
                                           starts, counts, impl=impl,
                                           **dict(zip(SCALES, out[2:])))
        x = x + attn.astype(x.dtype).reshape(r, c, h * hd) @ p["wo"][l]
    last = x[jnp.arange(r), jnp.clip(counts - 1, 0, c - 1)]
    return last @ p["embed"].T, [jnp.stack(a) for a in zip(*new)]


def _layerwise_decode(model, pools, table, lengths, tokens, active):
    """One decode step, layer by layer over sliced pools (as above)."""
    p, impl, (h, kvh, hd) = model.params, model.impl, (model.h, model.kvh,
                                                       model.hd)
    b = tokens.shape[0]
    x = jnp.take(p["embed"], tokens, axis=0)
    new_len = lengths + active.astype(lengths.dtype)
    new = []
    for l in range(model.cfg.n_layers):
        k, v, *scales = [a[l] for a in pools]
        kn = (x @ p["wk"][l]).reshape(b, kvh, hd)
        vn = (x @ p["wv"][l]).reshape(b, kvh, hd)
        out = ops.paged_kv_append(k, v, kn, vn, table, lengths, active,
                                  impl=impl, **dict(zip(SCALES, scales)))
        out = out[:2] + out[3:]
        new.append(out)
        q = (x @ p["wq"][l]).reshape(b, h, hd)
        attn = ops.paged_decode_attention(q, out[0], out[1], table, new_len,
                                          impl=impl,
                                          **dict(zip(SCALES, out[2:])))
        x = x + attn.reshape(b, h * hd) @ p["wo"][l]
    return x @ p["embed"].T, [jnp.stack(a) for a in zip(*new)]


def _pools(cache):
    out = [cache.k_pages, cache.v_pages]
    if cache.quantized:
        out += [cache.k_scale, cache.v_scale]
    return [np.asarray(a) for a in out]


@pytest.mark.parametrize("impl", ["ref", "pallas"])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_engine_writes_each_layer_into_its_own_pages(kv, impl):
    """A prefill chunk and three decode steps through the engine, which
    addresses layer ``l``'s page ``j`` as row ``l·P + j`` of one pool, leave
    every layer's K/V (and scales) bitwise what the same ops write into that
    layer's own sliced pool; the pages no slot maps stay as created in every
    layer.  Three layers, so an offset of ``P`` alone cannot pass."""
    cfg = dataclasses.replace(CFG, n_layers=3)
    model = PagedLM(cfg, jax.random.PRNGKey(3), impl=impl, kv_dtype=kv)
    cache = PagedKVCache.create(cfg, batch=3, max_len=16, page=4,
                                kv_dtype=kv)
    cache = cache.allocate(0, 3).allocate(2, 3)
    fresh = _pools(cache)
    mapped = sorted(set(cache.page_table_host[[0, 2], :3].ravel()))
    rng = np.random.default_rng(7)
    tokens = rng.integers(0, cfg.vocab, (2, 8)).astype(np.int32)
    counts, seqs = np.asarray([5, 7], np.int32), np.asarray([0, 2], np.int32)
    starts = np.zeros(2, np.int32)

    want_lg, want = jax.jit(_layerwise_prefill, static_argnums=(0, 7))(
        model, _pools(cache), cache.page_table, tokens, counts, seqs, starts,
        2)
    lg, cache = model.prefill_batch(tokens, counts, seqs, starts, cache)
    np.testing.assert_array_equal(np.asarray(lg), np.asarray(want_lg))
    for got, w in zip(_pools(cache), want):
        np.testing.assert_array_equal(got, np.asarray(w))

    active = jnp.asarray([True, False, True])
    feed = jnp.asarray([1, 0, 2], jnp.int32)
    for _ in range(3):
        want_lg, want = jax.jit(_layerwise_decode, static_argnums=0)(
            model, _pools(cache), cache.page_table, cache.lengths, feed,
            active)
        lg, cache = model.decode_step(feed, cache, active)
        np.testing.assert_array_equal(np.asarray(lg), np.asarray(want_lg))
        for got, w in zip(_pools(cache), want):
            np.testing.assert_array_equal(got, np.asarray(w))
        feed = jnp.argmax(lg[:, :cfg.vocab], axis=-1).astype(jnp.int32)

    free = np.setdiff1d(np.arange(cache.total_pages), mapped)
    assert free.size
    for got, f in zip(_pools(cache), fresh):
        np.testing.assert_array_equal(got[:, free], f[:, free])
