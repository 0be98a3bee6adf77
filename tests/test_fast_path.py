"""Device-resident decode fast path: fused multi-step decode bitwise equals
the step-at-a-time path, batched prefill equals sequential chunks, the
bounded chunk-write op matches its oracle, the length-adaptive kernel stays
correct on ragged batches, and the hot path never recompiles in steady
state."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.kernels import ops
from repro.serve import (
    PagedKVCache,
    PagedLM,
    Request,
    RequestState,
    Scheduler,
    static_batch_generate,
)

CFG = smoke_config("yi-6b")
MODEL = PagedLM(CFG, jax.random.PRNGKey(0), impl="ref")


def _prefilled(model, prompts, max_new, page=4, max_len=32):
    """Build a cache with every prompt prefilled (same bits every call)."""
    cache = PagedKVCache.create(
        CFG, batch=len(prompts), max_len=max_len, page=page
    )
    last = None
    for i, prompt in enumerate(prompts):
        cache = cache.allocate(i, cache.pages_for(len(prompt) + max_new))
        for start in range(0, len(prompt), 4):
            count = min(4, len(prompt) - start)
            buf = np.zeros((4,), np.int32)
            buf[:count] = prompt[start:start + count]
            logits, cache = model.prefill_chunk(
                jnp.asarray(buf), count, i, start, cache
            )
            last = logits
    return cache, last


def _prompts(rng, lens):
    return [rng.integers(0, CFG.vocab, n).astype(np.int32) for n in lens]


# ---------------------------------------------------------------------------
# Fused decode ≡ sequential decode (the tentpole equivalence)
# ---------------------------------------------------------------------------


def test_decode_steps_bitwise_equals_sequential_decode_step():
    rng = np.random.default_rng(0)
    prompts = _prompts(rng, (5, 7))
    n = 4

    cache_a, _ = _prefilled(MODEL, prompts, n + 1)
    cache_b, _ = _prefilled(MODEL, prompts, n + 1)
    tokens = np.asarray([3, 11], np.int32)
    active = np.asarray([True, True])

    # Sequential: n × decode_step with host-side argmax feeding back.
    seq_toks = []
    feed = tokens
    for _ in range(n):
        logits, cache_a = MODEL.decode_step(
            jnp.asarray(feed), cache_a, jnp.asarray(active)
        )
        feed = np.argmax(
            np.asarray(logits)[:, : CFG.vocab], axis=-1
        ).astype(np.int32)
        seq_toks.append(feed.copy())

    # Fused: one decode_steps launch with device-side argmax.
    fused, cache_b = MODEL.decode_steps(tokens, cache_b, active, n)
    np.testing.assert_array_equal(np.asarray(fused), np.stack(seq_toks))
    # Cache state (lengths + host shadow) advanced identically.
    np.testing.assert_array_equal(
        np.asarray(cache_a.lengths), np.asarray(cache_b.lengths)
    )
    np.testing.assert_array_equal(cache_a.lengths_host, cache_b.lengths_host)
    np.testing.assert_allclose(
        np.asarray(cache_a.k_pages), np.asarray(cache_b.k_pages)
    )


def test_decode_steps_inactive_slots_untouched():
    rng = np.random.default_rng(1)
    prompts = _prompts(rng, (6, 6))
    cache, _ = _prefilled(MODEL, prompts, 4)
    before = np.asarray(cache.lengths).copy()
    active = np.asarray([True, False])
    toks, cache = MODEL.decode_steps(
        np.asarray([1, 2], np.int32), cache, active, 3
    )
    after = np.asarray(cache.lengths)
    assert after[0] == before[0] + 3
    assert after[1] == before[1]          # inactive slot appended nothing
    np.testing.assert_array_equal(cache.lengths_host, after)


# ---------------------------------------------------------------------------
# Batched prefill ≡ sequential single-sequence chunks
# ---------------------------------------------------------------------------


def test_prefill_batch_bitwise_equals_sequential_chunks():
    rng = np.random.default_rng(2)
    prompts = _prompts(rng, (6, 3, 9))
    cache_a = PagedKVCache.create(CFG, batch=3, max_len=32, page=4)
    cache_b = PagedKVCache.create(CFG, batch=3, max_len=32, page=4)
    for i, p in enumerate(prompts):
        cache_a = cache_a.allocate(i, cache_a.pages_for(len(p)))
        cache_b = cache_b.allocate(i, cache_b.pages_for(len(p)))

    chunk = 4
    # Sequential: one prefill_chunk per sequence per chunk position.
    logits_a = {}
    for i, p in enumerate(prompts):
        for start in range(0, len(p), chunk):
            count = min(chunk, len(p) - start)
            buf = np.zeros((chunk,), np.int32)
            buf[:count] = p[start:start + count]
            lg, cache_a = MODEL.prefill_chunk(
                jnp.asarray(buf), count, i, start, cache_a
            )
            logits_a[i] = np.asarray(lg)

    # Batched: all sequences advance one chunk per call (padding rows once a
    # short prompt is done).
    logits_b = {}
    maxlen = max(len(p) for p in prompts)
    for start in range(0, maxlen, chunk):
        toks = np.zeros((3, chunk), np.int32)
        counts = np.zeros((3,), np.int32)
        slots = np.arange(3, dtype=np.int32)
        starts = np.full((3,), start, np.int32)
        for i, p in enumerate(prompts):
            count = max(0, min(chunk, len(p) - start))
            toks[i, :count] = p[start:start + count]
            counts[i] = count
        lg, cache_b = MODEL.prefill_batch(toks, counts, slots, starts, cache_b)
        lg = np.asarray(lg)
        for i, p in enumerate(prompts):
            if counts[i] and start + counts[i] == len(p):
                logits_b[i] = lg[i]

    for i in range(3):
        np.testing.assert_array_equal(logits_a[i], logits_b[i])
    np.testing.assert_array_equal(
        np.asarray(cache_a.k_pages), np.asarray(cache_b.k_pages)
    )
    np.testing.assert_array_equal(
        np.asarray(cache_a.lengths), np.asarray(cache_b.lengths)
    )


def test_prefill_batch_padding_rows_never_nan():
    """counts==0 padding rows are fully masked under the bounded-context
    mask; the finite mask constant (not ``-inf``) keeps their softmax
    NaN-free, so padding can never poison the donated pools.  The padding
    slot's pool pages and the real rows' logits must be untouched."""
    rng = np.random.default_rng(10)
    prompts = _prompts(rng, (6,))
    cache = PagedKVCache.create(CFG, batch=2, max_len=32, page=4)
    cache = cache.allocate(0, cache.pages_for(6))
    toks = np.zeros((2, 4), np.int32)
    toks[0] = prompts[0][:4]
    logits, cache = MODEL.prefill_batch(
        toks, np.asarray([4, 0], np.int32), np.asarray([0, 1], np.int32),
        np.asarray([0, 0], np.int32), cache,
    )
    assert np.isfinite(np.asarray(logits)).all()
    assert np.isfinite(np.asarray(cache.k_pages)).all()
    assert np.isfinite(np.asarray(cache.v_pages)).all()
    assert int(np.asarray(cache.lengths)[1]) == 0
    # Bitwise identical to the same prefill without the padding row.
    cache_b = PagedKVCache.create(CFG, batch=2, max_len=32, page=4)
    cache_b = cache_b.allocate(0, cache_b.pages_for(6))
    lg, cache_b = MODEL.prefill_batch(
        toks[:1], np.asarray([4], np.int32), np.asarray([0], np.int32),
        np.asarray([0], np.int32), cache_b,
    )
    np.testing.assert_array_equal(np.asarray(logits)[0], np.asarray(lg)[0])
    np.testing.assert_array_equal(
        np.asarray(cache.k_pages), np.asarray(cache_b.k_pages)
    )


def test_prefill_cache_is_lru_bounded():
    """Ragged (page, ctx) traffic mints jitted prefill programs; the cache
    must never exceed its cap, evicting least-recently-used buckets (an
    evicted bucket re-jits on demand — correctness never depends on
    residency)."""
    model = PagedLM(CFG, jax.random.PRNGKey(2), impl="ref",
                    prefill_cache_cap=3)
    rng = np.random.default_rng(11)
    keys_seen = []
    for page in (1, 2, 4, 8, 16):
        prompt = rng.integers(0, CFG.vocab, 8).astype(np.int32)
        cache = PagedKVCache.create(CFG, batch=1, max_len=16, page=page)
        cache = cache.allocate(0, cache.pages_for(len(prompt)))
        for start in range(0, 8, 4):
            _, cache = model.prefill_chunk(
                jnp.asarray(prompt[start:start + 4]), 4, 0, start, cache
            )
        keys_seen.extend(k for k in model._prefill_cache
                         if k not in keys_seen)
        assert len(model._prefill_cache) <= 3
    assert len(keys_seen) > 3                      # sweep really minted > cap
    # LRU order: the most recent buckets survive, the oldest were evicted.
    assert list(model._prefill_cache) == keys_seen[-3:]
    # An evicted bucket still works (recompiles transparently).
    page = 1
    prompt = rng.integers(0, CFG.vocab, 4).astype(np.int32)
    cache = PagedKVCache.create(CFG, batch=1, max_len=16, page=page)
    cache = cache.allocate(0, cache.pages_for(4))
    logits, _ = model.prefill_chunk(jnp.asarray(prompt), 4, 0, 0, cache)
    assert np.isfinite(np.asarray(logits)).all()
    assert len(model._prefill_cache) == 3


@pytest.mark.parametrize("lens", [
    (4, 8),          # exact page multiples (page=4)
    (16,),           # exactly fills ctx_pages (max_len)
    (12, 3, 16),     # page-multiple, sub-page, and full-table mixed
])
def test_prefill_boundary_lengths_match_sequential(lens):
    """Prompts ending exactly on page boundaries / exactly filling the
    page-table row: the pow2 ctx bucketing must cover the final page
    (the off-by-one spot) and stay bitwise equal to sequential chunks."""
    rng = np.random.default_rng(12)
    prompts = _prompts(rng, lens)
    b = len(prompts)
    cache_a = PagedKVCache.create(CFG, batch=b, max_len=16, page=4)
    cache_b = PagedKVCache.create(CFG, batch=b, max_len=16, page=4)
    for i, p in enumerate(prompts):
        cache_a = cache_a.allocate(i, cache_a.pages_for(len(p)))
        cache_b = cache_b.allocate(i, cache_b.pages_for(len(p)))
    chunk = 4
    logits_a = {}
    for i, p in enumerate(prompts):
        for start in range(0, len(p), chunk):
            count = min(chunk, len(p) - start)
            buf = np.zeros((chunk,), np.int32)
            buf[:count] = p[start:start + count]
            lg, cache_a = MODEL.prefill_chunk(
                jnp.asarray(buf), count, i, start, cache_a
            )
            logits_a[i] = np.asarray(lg)
    logits_b = {}
    for start in range(0, max(lens), chunk):
        toks = np.zeros((b, chunk), np.int32)
        counts = np.zeros((b,), np.int32)
        for i, p in enumerate(prompts):
            count = max(0, min(chunk, len(p) - start))
            toks[i, :count] = p[start:start + count]
            counts[i] = count
        lg, cache_b = MODEL.prefill_batch(
            toks, counts, np.arange(b, dtype=np.int32),
            np.full((b,), start, np.int32), cache_b,
        )
        for i, p in enumerate(prompts):
            if counts[i] and start + counts[i] == len(p):
                logits_b[i] = np.asarray(lg)[i]
    for i in range(b):
        np.testing.assert_array_equal(logits_a[i], logits_b[i])
    np.testing.assert_array_equal(
        np.asarray(cache_a.k_pages), np.asarray(cache_b.k_pages)
    )
    np.testing.assert_array_equal(
        np.asarray(cache_a.lengths), np.asarray(cache_b.lengths)
    )


# ---------------------------------------------------------------------------
# Bounded chunk write op vs oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("starts,counts", [
    ([0, 5, 14], [6, 0, 2]),       # page-straddling, padding row, tail write
    ([3, 0, 7], [1, 6, 6]),        # single row, full chunk, cross-page
])
def test_paged_kv_write_chunk_pallas_matches_ref(starts, counts):
    rng = np.random.default_rng(3)
    pool, page, kvh, d, b, npg, c = 16, 4, 2, 16, 3, 4, 6
    kp = jnp.asarray(rng.normal(size=(pool, page, kvh, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(pool, page, kvh, d)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(b, c, kvh, d)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(b, c, kvh, d)), jnp.float32)
    rows = jnp.asarray(rng.permutation(pool)[: b * npg].reshape(b, npg),
                       jnp.int32)
    st = jnp.asarray(starts, jnp.int32)
    ct = jnp.asarray(counts, jnp.int32)
    outs = [
        ops.paged_kv_write_chunk(kp, vp, kn, vn, rows, st, ct, impl=im)
        for im in ("ref", "pallas")
    ]
    for a, b_ in zip(outs[0], outs[1]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


def test_paged_kv_write_chunk_never_clobbers_other_pages():
    """A stale copy of an untouched window page must not overwrite another
    sequence's write to that same physical page (the scatter-back of junk
    window slots is routed out of bounds)."""
    rng = np.random.default_rng(4)
    pool, page, kvh, d, c = 8, 4, 1, 8, 4
    kp = jnp.zeros((pool, page, kvh, d), jnp.float32)
    vp = jnp.zeros((pool, page, kvh, d), jnp.float32)
    # Row 0's window [its page, +1 junk] — the junk table entry is 0, which
    # is row 1's *real* page being written in the same call.
    rows = jnp.asarray([[5, 0], [0, 3]], jnp.int32)
    kn = jnp.asarray(rng.normal(size=(2, c, kvh, d)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(2, c, kvh, d)), jnp.float32)
    st = jnp.asarray([0, 0], jnp.int32)
    ct = jnp.asarray([2, 4], jnp.int32)
    k2, _ = ops.paged_kv_write_chunk(kp, vp, kn, vn, rows, st, ct,
                                     impl="pallas")
    np.testing.assert_allclose(np.asarray(k2[0, :4]), np.asarray(kn[1]))
    np.testing.assert_allclose(np.asarray(k2[5, :2]), np.asarray(kn[0, :2]))


# ---------------------------------------------------------------------------
# Length-adaptive kernel on ragged batches
# ---------------------------------------------------------------------------


def test_paged_decode_attention_length_adaptive_matches_ref():
    rng = np.random.default_rng(5)
    pool, page, kvh, d, b, npg, h = 16, 4, 2, 32, 4, 4, 8
    kp = jnp.asarray(rng.normal(size=(pool, page, kvh, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(pool, page, kvh, d)), jnp.float32)
    table = jnp.asarray(rng.permutation(pool).reshape(b, npg), jnp.int32)
    # Fully empty, partial first page, exact page multiple, full table.
    lengths = jnp.asarray([0, 3, 8, 16], jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    got = ops.paged_decode_attention(q, kp, vp, table, lengths)
    want = ops.paged_decode_attention(q, kp, vp, table, lengths, impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(got[0]).max()) == 0.0  # empty sequence → zeros


# ---------------------------------------------------------------------------
# No recompilation across steps (jit compilation-cache counters)
# ---------------------------------------------------------------------------


def test_decode_fast_path_does_not_recompile_across_steps():
    model = PagedLM(CFG, jax.random.PRNGKey(1), impl="ref")
    rng = np.random.default_rng(6)
    prompts = _prompts(rng, (5, 9))

    def run():
        cache = PagedKVCache.create(CFG, batch=2, max_len=32, page=4)
        sched = Scheduler(model, cache, chunk=4)
        for i, p in enumerate(prompts):
            sched.submit(Request(rid=i, prompt=p, max_new=10))
        return sched.run()

    first = run()
    fused = model._decode_many._cache_size()
    prefill = sum(f._cache_size() for f in model._prefill_cache.values())
    # Fused launches are pow2-bucketed: at most log2(page)+log2(max_new)+2
    # distinct scan lengths ever compile.
    assert fused <= 6
    second = run()
    assert second == first
    assert model._decode_many._cache_size() == fused  # zero new compiles
    assert sum(f._cache_size() for f in model._prefill_cache.values()) \
        == prefill


def test_scheduler_syncs_only_at_boundaries():
    """In steady-state decode the fused path must cover multiple model steps
    per scheduler iteration (i.e. per host sync)."""
    rng = np.random.default_rng(7)
    prompts = _prompts(rng, (4,))
    cache = PagedKVCache.create(CFG, batch=1, max_len=64, page=8)
    sched = Scheduler(MODEL, cache, chunk=8)
    fam = sched.family
    launches = []  # (scheduler step, fused model steps) per decode call
    decode_steps = fam.decode_steps

    def count(tokens, active, n):
        launches.append((sched._step, int(n)))
        return decode_steps(tokens, active, n)

    fam.decode_steps = count
    sched.submit(Request(rid=0, prompt=prompts[0], max_new=16))
    sched.run()
    steps = sum(n for _, n in launches)
    sched_iters = len({s for s, _ in launches})
    assert steps == sched.stats.decode_steps == 15  # max_new - 1 model steps
    assert sched_iters < steps                      # fused: fewer syncs


def test_lookahead_pages_reclaimed_for_late_submission():
    """Lookahead prealloc maps pages for residents' whole remaining
    generations once the queue drains; a request submitted *after* that must
    still be admitted promptly — admission reclaims the unwritten lookahead
    pages instead of waiting for the holder to retire."""
    rng = np.random.default_rng(9)
    pa, pb, pc = _prompts(rng, (4, 4, 8))
    cache = PagedKVCache.create(CFG, batch=2, max_len=16, page=4,
                                pool_pages=6)
    sched = Scheduler(MODEL, cache, chunk=4)
    ra = Request(rid=0, prompt=pa, max_new=13)  # long-lived: peaks at 4 pages
    rb = Request(rid=1, prompt=pb, max_new=2)   # retires after one step
    sched.submit(ra)
    sched.submit(rb)
    sched.step()  # prefill both; lookahead maps A's remaining pages; B done
    assert rb.state is RequestState.FINISHED
    assert sched.cache._mapped(ra.slot) == 4  # A holds its full lookahead
    assert sched.cache.n_free == 2            # not enough for C (needs 3)
    rc = Request(rid=2, prompt=pc, max_new=2)
    sched.submit(rc)
    sched.step()
    assert rc.state is not RequestState.WAITING  # admitted via reclaim
    got = sched.run()
    # Every output still matches the static reference (row-wise model: C's
    # tokens are independent of its batch placement).
    want = static_batch_generate(
        MODEL, PagedKVCache.create(CFG, batch=2, max_len=32, page=4),
        [pa, pb], 13, chunk=4,
    )
    want_c = static_batch_generate(
        MODEL, PagedKVCache.create(CFG, batch=1, max_len=32, page=4),
        [pc], 2, chunk=4,
    )
    assert got[0] == want[0]
    assert got[1] == want[1][:2]
    assert got[2] == want_c[0]


# ---------------------------------------------------------------------------
# Fast path slots into the full scheduler (spot-check vs static batch)
# ---------------------------------------------------------------------------


def test_fused_scheduler_matches_static_batch_large_page():
    rng = np.random.default_rng(8)
    prompts = _prompts(rng, (11, 2, 7))
    max_new = 13

    cache_ref = PagedKVCache.create(CFG, batch=3, max_len=64, page=16)
    want = static_batch_generate(MODEL, cache_ref, prompts, max_new, chunk=8)

    cache = PagedKVCache.create(CFG, batch=3, max_len=64, page=16,
                                pool_pages=7)
    sched = Scheduler(MODEL, cache, chunk=8)
    for i, p in enumerate(prompts):
        sched.submit(Request(rid=i, prompt=p, max_new=max_new))
    got = sched.run()
    assert got == {i: want[i] for i in want}


# ---------------------------------------------------------------------------
# Pallas prefill kernel slotted into the engine (vs the einsum ref oracle)
# ---------------------------------------------------------------------------


def test_prefill_batch_pallas_kernel_matches_ref_engine():
    """The full engine prefill step (embed → chunk write → paged prefill
    attention → logits) with impl='pallas' stays allclose to the einsum ref
    path, including a padding row and a mid-page start."""
    model_p = PagedLM(CFG, jax.random.PRNGKey(0), impl="pallas")
    rng = np.random.default_rng(13)
    prompts = _prompts(rng, (6, 9))
    caches = {}
    logits = {}
    for impl, model in (("ref", MODEL), ("pallas", model_p)):
        cache = PagedKVCache.create(CFG, batch=3, max_len=32, page=4)
        for i, p in enumerate(prompts):
            cache = cache.allocate(i, cache.pages_for(len(p)))
        toks = np.zeros((3, 4), np.int32)
        toks[0] = prompts[0][:4]
        toks[1] = prompts[1][:4]
        lg, cache = model.prefill_batch(
            toks, np.asarray([4, 4, 0], np.int32),
            np.asarray([0, 1, 2], np.int32),
            np.asarray([0, 0, 0], np.int32), cache,
        )
        # Second chunk: ragged counts, rows at different positions.
        toks = np.zeros((3, 4), np.int32)
        toks[0, :2] = prompts[0][4:6]
        toks[1] = prompts[1][4:8]
        lg, cache = model.prefill_batch(
            toks, np.asarray([2, 4, 0], np.int32),
            np.asarray([0, 1, 2], np.int32),
            np.asarray([4, 4, 0], np.int32), cache,
        )
        caches[impl], logits[impl] = cache, np.asarray(lg)
    np.testing.assert_allclose(
        logits["pallas"][:2], logits["ref"][:2], rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(caches["pallas"].k_pages),
        np.asarray(caches["ref"].k_pages), rtol=1e-5, atol=1e-5,
    )


def test_scheduler_with_pallas_prefill_kernel_matches_static_batch():
    """End-to-end: continuous batching with the Pallas prefill kernel (and
    Pallas decode/append) reproduces the static reference token-for-token —
    greedy decode is bit-stable across the kernel/ref numerics here."""
    model_p = PagedLM(CFG, jax.random.PRNGKey(0), impl="pallas")
    rng = np.random.default_rng(14)
    prompts = _prompts(rng, (9, 4))
    max_new = 5
    want = static_batch_generate(
        MODEL, PagedKVCache.create(CFG, batch=2, max_len=32, page=4),
        prompts, max_new, chunk=4,
    )
    cache = PagedKVCache.create(CFG, batch=2, max_len=32, page=4)
    sched = Scheduler(model_p, cache, chunk=4)
    for i, p in enumerate(prompts):
        sched.submit(Request(rid=i, prompt=p, max_new=max_new))
    got = sched.run()
    assert got == {i: want[i] for i in want}
