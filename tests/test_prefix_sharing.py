"""Property-based invariant fuzzer for prefix sharing + copy-on-write.

Random overlapping-prefix traffic drives the scheduler end-to-end (admission
→ prefill → decode → evict → replay → retire) and the pool's ownership
invariants are asserted after *every* scheduler step:

* **refcount conservation** — the refcount total equals the page-table
  mappings (per-slot ``mapped``) plus the prefix index's retentions; no
  page is simultaneously free and owned; free + owned partition the pool.
* **no leaks** — after the run drains and the prefix cache is flushed, the
  pool is back to all-free with every refcount at zero.
* **bit-for-bit outputs** — the sharing scheduler, the non-sharing
  scheduler, and ``static_batch_generate`` agree exactly, fp32 and int8
  (the replay contract: shared mappings are re-derived, never re-filled
  differently).

Runs under the real ``hypothesis`` package or the deterministic stub in
tests/_hypothesis_stub.py (CI runs both, the stub leg with
``REPRO_STUB_MAX_EXAMPLES=25``).  Alongside the fuzzer sit deterministic
regressions for the sharp edges: copy-on-write on fully page-aligned
matches, ``trim`` on shared pages (decrement, never free), retained-prefix
reuse after retirement, in-flight admission deferral, and an aligned match
that outgrows the pool.
"""
import jax
import numpy as np
import pytest
from hypothesis import HealthCheck, given, settings, strategies as st

from repro.configs import smoke_config
from repro.serve import (
    FaultPlan,
    PagedKVCache,
    PagedLM,
    Request,
    Scheduler,
    check_scheduler_invariants,
    static_batch_generate,
)

CFG = smoke_config("yi-6b")
PAGE = 4
MAX_LEN = 32
MODELS = {
    "fp32": PagedLM(CFG, jax.random.PRNGKey(0), impl="ref"),
    "int8": PagedLM(CFG, jax.random.PRNGKey(0), impl="ref", kv_dtype="int8"),
}
KV_DTYPE = {"fp32": None, "int8": "int8"}


def check_invariants(sched: Scheduler, requests=None) -> None:
    # The full oracle lives in repro.serve.faults (conservation, free/owned
    # partition, slot bookkeeping, terminal-state discipline); raising
    # InvariantViolation (an AssertionError) keeps pytest semantics.
    check_scheduler_invariants(sched, requests)


def drive(sched: Scheduler, requests, max_steps: int = 400):
    """sched.run(), but with the invariants checked after every step."""
    for r in requests:
        sched.submit(r)
    steps = 0
    while sched.queue or sched.resident:
        sched.step()
        check_invariants(sched, requests)
        steps += 1
        assert steps < max_steps, "scheduler stalled"
    return {rid: r.generated for rid, r in sorted(sched.finished.items())}


def make_prompts(rng, n_reqs: int, sys_pages: int, max_new: int):
    """Overlapping-prefix mix: a shared system prompt (``sys_pages`` full
    pages) with random tails, plus occasional fully-random prompts."""
    sys_prompt = rng.integers(0, CFG.vocab, sys_pages * PAGE, dtype=np.int64)
    prompts = []
    for _ in range(n_reqs):
        if sys_pages and rng.random() < 0.75:
            tail = rng.integers(0, CFG.vocab, int(rng.integers(0, 6)),
                                dtype=np.int64)
            p = np.concatenate([sys_prompt, tail])
        else:
            p = rng.integers(0, CFG.vocab, int(rng.integers(1, 11)),
                             dtype=np.int64)
        p = p if len(p) else rng.integers(0, CFG.vocab, 1, dtype=np.int64)
        assert len(p) + max_new - 1 <= MAX_LEN
        prompts.append(np.asarray(p, np.int32))
    return prompts


@settings(max_examples=25, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(
    seed=st.integers(min_value=0, max_value=10**6),
    n_reqs=st.integers(min_value=1, max_value=4),
    sys_pages=st.integers(min_value=0, max_value=2),
    max_new=st.integers(min_value=1, max_value=4),
    pool_extra=st.integers(min_value=0, max_value=6),
    kv=st.sampled_from(["fp32", "int8"]),
    chaos=st.booleans(),
)
def test_random_traffic_invariants_and_equivalence(
    seed, n_reqs, sys_pages, max_new, pool_extra, kv, chaos
):
    rng = np.random.default_rng(seed)
    prompts = make_prompts(rng, n_reqs, sys_pages, max_new)
    model = MODELS[kv]
    batch = min(n_reqs, 3)
    # Pool from tight (worst single request — maximum eviction/replay and
    # retention-drop pressure) to roomy.
    worst = max(-(-(len(p) + max_new - 1) // PAGE) for p in prompts)
    pool = worst + pool_extra
    reqs = lambda: [
        Request(rid=i, prompt=p, max_new=max_new)
        for i, p in enumerate(prompts)
    ]
    # Chaos leg: a seeded fault plan (forced exhaustion, denied allocations,
    # prefix drops) runs under BOTH schedulers — faults degrade scheduling,
    # never outputs, so every equality below must still hold.
    faults = FaultPlan.random(seed + 1, n_steps=16) if chaos else None

    def run(sharing: bool):
        cache = PagedKVCache.create(
            CFG, batch=batch, max_len=MAX_LEN, page=PAGE,
            pool_pages=pool, kv_dtype=KV_DTYPE[kv],
        )
        sched = Scheduler(model, cache, chunk=3, prefix_sharing=sharing,
                          faults=faults)
        return drive(sched, reqs()), sched

    out_shared, sched = run(True)
    out_plain, _ = run(False)
    assert out_shared == out_plain, "sharing changed outputs"

    static_cache = PagedKVCache.create(
        CFG, batch=n_reqs, max_len=MAX_LEN, page=PAGE,
        pool_pages=n_reqs * (MAX_LEN // PAGE), kv_dtype=KV_DTYPE[kv],
    )
    static = static_batch_generate(model, static_cache, prompts, max_new,
                                   chunk=3)
    assert out_shared == dict(static), "scheduler diverged from static batch"

    # No leaks: drained run + flushed prefix cache → pool all-free.
    check_invariants(sched)
    sched.flush_prefix_cache()
    assert sorted(sched.cache.free) == list(range(pool))
    assert int(sched.cache.refcounts.sum()) == 0
    # Accounting coherence: sharing recorded ⇔ pages were shared.
    assert (sched.stats.prefill_tokens_saved > 0) == (
        sched.stats.shared_pages > 0
    )


@settings(max_examples=25, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(
    seed=st.integers(min_value=0, max_value=10**6),
    n_ops=st.integers(min_value=1, max_value=60),
)
def test_refcount_lifecycle_fuzz(seed, n_ops):
    """Engine-level fuzz of allocate/share/trim/release/retain/CoW: refcount
    conservation holds after every operation, with the retained set tracked
    shadow-side (no model, no scheduler — the bookkeeping alone)."""
    rng = np.random.default_rng(seed)
    batch, pool = 3, 10
    cache = PagedKVCache.create(
        CFG, batch=batch, max_len=MAX_LEN, page=PAGE, pool_pages=pool
    )
    retained: list = []

    def conserved():
        assert int(cache.refcounts.sum()) == (
            int(cache.mapped.sum()) + len(retained)
        )
        owned = {p for p in range(pool) if cache.refcounts[p] > 0}
        assert not (owned & set(cache.free))
        assert len(owned) + len(cache.free) == pool

    for _ in range(n_ops):
        op = rng.choice(["alloc", "share", "trim", "release", "retain",
                         "unretain", "cow"])
        seq = int(rng.integers(0, batch))
        used = int(cache.mapped[seq])
        if op == "alloc":
            n = int(rng.integers(1, 3))
            if n <= cache.n_free and used + n <= cache.pages_per_seq:
                cache = cache.allocate(seq, n)
        elif op == "share":
            src = int(rng.integers(0, batch))
            n_src = int(cache.mapped[src])
            if src != seq and n_src and used + n_src <= cache.pages_per_seq:
                ids = [int(p) for p in cache.page_table_host[src, :n_src]]
                cache = cache.share(seq, ids)
        elif op == "trim":
            cache = cache.trim(seq, int(rng.integers(0, used + 1)))
        elif op == "release":
            cache = cache.release(seq)
        elif op == "retain" and used:
            p = int(cache.page_table_host[seq, int(rng.integers(0, used))])
            cache = cache.retain_pages([p])
            retained.append(p)
        elif op == "unretain" and retained:
            p = retained.pop(int(rng.integers(0, len(retained))))
            cache = cache.release_pages([p])
        elif op == "cow" and used:
            hi = used * PAGE - 1
            try:
                cache, _ = cache.ensure_writable(seq, 0, hi)
            except Exception as e:
                assert "copy-on-write needs" in str(e)
        conserved()

    for seq in range(batch):
        cache = cache.release(seq)
    cache = cache.release_pages(retained)
    retained.clear()
    conserved()
    assert sorted(cache.free) == list(range(pool))


def test_cow_of_a_page_one_slot_maps_twice():
    """A slot that shares its own page (table ``[8, 8]``, two of the page's
    owners) copies it once: the first copy leaves the slot the second
    mapping's only owner, which is then written in place, and no page is
    left owned by nobody and missing from the free list."""
    pool = 10
    cache = PagedKVCache.create(CFG, batch=2, max_len=MAX_LEN, page=PAGE,
                                pool_pages=pool)
    cache = cache.allocate(0, 1)
    own = int(cache.page_table_host[0, 0])
    cache = cache.share(0, [own])
    assert list(cache.page_table_host[0, :2]) == [own, own]
    cache, copied = cache.ensure_writable(0, 0, 2 * PAGE - 1)
    assert copied == 1
    owned = {p for p in range(pool) if cache.refcounts[p] > 0}
    assert owned == set(int(p) for p in cache.page_table_host[0, :2])
    assert len(owned) == 2 and not owned & set(cache.free)
    assert len(owned) + len(cache.free) == pool


def test_cow_on_page_aligned_full_match():
    """A prompt that fully matches a page-multiple indexed prefix must
    copy-on-write its final shared page (the re-prefilled last token writes
    there) — and still reproduce the unshared outputs exactly."""
    model = MODELS["fp32"]
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, CFG.vocab, 2 * PAGE).astype(np.int32)
    prompts = [prompt.copy(), prompt.copy(), prompt.copy()]

    def run(sharing):
        cache = PagedKVCache.create(CFG, batch=3, max_len=MAX_LEN, page=PAGE)
        sched = Scheduler(model, cache, chunk=3, prefix_sharing=sharing)
        return drive(sched, [
            Request(rid=i, prompt=p, max_new=3)
            for i, p in enumerate(prompts)
        ]), sched

    out_shared, sched = run(True)
    out_plain, _ = run(False)
    assert out_shared == out_plain
    assert sched.stats.cow_copies >= 1, "full-page match must trigger CoW"
    assert sched.stats.prefill_tokens_saved > 0


def test_trim_shared_page_decrements_not_frees():
    """Regression (the shared-page trim bug): trimming a sequence whose
    pages a prefix sibling still references must drop only this sequence's
    ownership — the page stays out of the free pool until the last owner
    lets go, and the sibling's KV mapping stays intact."""
    cache = PagedKVCache.create(CFG, batch=2, max_len=MAX_LEN, page=PAGE,
                                pool_pages=8)
    cache = cache.allocate(0, 2)
    pages = [int(p) for p in cache.page_table_host[0, :2]]
    cache = cache.share(1, pages)
    assert all(cache.refcounts[p] == 2 for p in pages)

    cache = cache.trim(0, 0)  # would free both pages without refcounts
    assert not (set(pages) & set(cache.free)), "trim freed shared pages"
    assert all(cache.refcounts[p] == 1 for p in pages)
    assert [int(p) for p in cache.page_table_host[1, :2]] == pages

    cache = cache.release(1)  # last owner → now they free
    assert set(pages) <= set(cache.free)
    assert int(cache.refcounts.sum()) == 0


def test_retained_prefix_reused_after_retirement():
    """The prefix cache outlives its author: a request admitted after the
    original has fully retired still maps the retained pages."""
    model = MODELS["fp32"]
    rng = np.random.default_rng(12)
    prompt = rng.integers(0, CFG.vocab, 2 * PAGE + 2).astype(np.int32)

    cache = PagedKVCache.create(CFG, batch=2, max_len=MAX_LEN, page=PAGE)
    sched = Scheduler(model, cache, chunk=4, prefix_sharing=True)
    first = drive(sched, [Request(rid=0, prompt=prompt, max_new=3)])
    assert not sched.resident and len(sched.prefix_index.entries) == 2

    second = drive(sched, [Request(rid=1, prompt=prompt.copy(), max_new=3)])
    assert sched.stats.prefill_tokens_saved >= 2 * PAGE
    assert second[1] == first[0]  # same prompt, same tokens


def test_aligned_match_larger_than_the_pool_prefills_privately():
    """A page-aligned match keeps its retained pages and adds a
    copy-on-write unit, which can exceed a pool that a private prefill
    fits: admission then drops the chain instead of stalling."""
    model = MODELS["fp32"]
    rng = np.random.default_rng(13)
    prompt = rng.integers(0, CFG.vocab, 2 * PAGE).astype(np.int32)
    longer = np.concatenate([prompt, [7]]).astype(np.int32)

    cache = PagedKVCache.create(CFG, batch=2, max_len=MAX_LEN, page=PAGE,
                                pool_pages=3)
    sched = Scheduler(model, cache, chunk=3, prefix_sharing=True)
    drive(sched, [Request(rid=0, prompt=longer, max_new=1)])
    assert len(sched.prefix_index.entries) == 2  # retained: 1 unit free
    out = drive(sched, [Request(rid=1, prompt=prompt, max_new=2)])

    plain = Scheduler(model, PagedKVCache.create(
        CFG, batch=2, max_len=MAX_LEN, page=PAGE, pool_pages=3), chunk=3)
    assert out[1] == drive(plain, [Request(rid=1, prompt=prompt,
                                           max_new=2)])[1]


def test_concurrent_identical_prompts_share_via_deferral():
    """Simultaneously submitted requests with one system prompt still share:
    admission defers the later arrivals one boundary while the first
    prefills, then maps its registered pages."""
    model = MODELS["fp32"]
    rng = np.random.default_rng(13)
    sys_prompt = rng.integers(0, CFG.vocab, 2 * PAGE)
    prompts = [
        np.concatenate([sys_prompt, rng.integers(0, CFG.vocab, t)])
        .astype(np.int32)
        for t in (2, 3, 4)
    ]
    cache = PagedKVCache.create(CFG, batch=3, max_len=MAX_LEN, page=PAGE)
    sched = Scheduler(model, cache, chunk=4, prefix_sharing=True)
    out = drive(sched, [
        Request(rid=i, prompt=p, max_new=2) for i, p in enumerate(prompts)
    ])
    assert sched.stats.prefill_tokens_saved >= 2 * 2 * PAGE  # rids 1 and 2
    plain_cache = PagedKVCache.create(CFG, batch=3, max_len=MAX_LEN,
                                      page=PAGE)
    plain = Scheduler(model, plain_cache, chunk=4)
    out_plain = drive(plain, [
        Request(rid=i, prompt=p.copy(), max_new=2)
        for i, p in enumerate(prompts)
    ])
    assert out == out_plain



@pytest.mark.parametrize("b", [2, 4, 8], ids=lambda b: f"b={b}")
def test_shared_system_prompt_saves_prefill(b):
    """``b`` prompts repeat one page-aligned 32-token system prompt with
    short distinct tails: sharing skips at least 40% of the prompt tokens'
    prefill, and the outputs are bit-for-bit the unshared run's."""
    page, chunk, max_len, max_new = 8, 8, 64, 8
    rng = np.random.default_rng(7)
    system = rng.integers(0, CFG.vocab, 4 * page)
    prompts = [np.concatenate([system, rng.integers(0, CFG.vocab, int(t))])
               .astype(np.int32) for t in rng.integers(4, 9, b)]

    def run(sharing):
        cache = PagedKVCache.create(CFG, batch=b, max_len=max_len, page=page)
        sched = Scheduler(MODELS["fp32"], cache, chunk=chunk,
                          prefix_sharing=sharing)
        out = drive(sched, [Request(rid=i, prompt=p, max_new=max_new)
                            for i, p in enumerate(prompts)])
        return out, sched.stats

    out, st = run(True)
    out_plain, st_plain = run(False)
    assert out == out_plain
    assert len(out) == b
    saved_frac = st.prefill_tokens_saved / sum(len(p) for p in prompts)
    assert saved_frac >= 0.4
    assert st.share_events == b - 1 and st_plain.share_events == 0


def test_prefix_sharing_requires_refcounted_cache():
    cache = PagedKVCache.create(CFG, batch=1, max_len=MAX_LEN, page=PAGE)
    import dataclasses
    legacy = dataclasses.replace(cache, refcounts=None)
    with pytest.raises(ValueError, match="refcounted"):
        Scheduler(MODELS["fp32"], legacy, prefix_sharing=True)
