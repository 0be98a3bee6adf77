"""Multi-query speculative verify: kernel vs oracle, acceptance logic,
engine- and scheduler-level bit-equivalence with plain greedy decode.

``ops.paged_verify`` scores K draft tokens per sequence in one clamped
scalar-prefetched page walk — structurally a causal prefill chunk whose
``starts`` are the live lengths — so the ref/pallas sweep here mirrors the
prefill sweep with the verify calling convention (per-row ``lengths`` +
``counts``, ragged and page-straddling).  ``ops.speculative_accept`` is the
greedy accept rule (longest matched draft prefix + the model's bonus
token); the engine/scheduler tests assert the one property everything
rests on: emitted tokens are bitwise the plain greedy decode sequence for
every ``spec_k``, drafter quality notwithstanding — including K=1 (the
degenerate no-draft path) and under eviction/replay chaos.
"""
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
from repro.serve.drafter import NGramDrafter, TinyLMDrafter
from repro.serve.faults import FaultPlan, check_scheduler_invariants

CFG = smoke_config("yi-6b")


def _sharpen(model):
    """Random-init smoke models collapse to a one-token greedy fixed point;
    amplified weights give varied sequences so equivalence is non-trivial."""
    model.params = {
        k: (v * 8.0 if k != "embed" else v * 3.0)
        for k, v in model.params.items()
    }
    return model


# ---------------------------------------------------------------------------
# Kernel vs oracle
# ---------------------------------------------------------------------------


def _verify_case(rng, b, k, h, kvh, d, pool, page, ctx, lengths, counts,
                 int8=False):
    kp = jnp.asarray(rng.normal(size=(pool, page, kvh, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(pool, page, kvh, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, k, h, d)), jnp.float32)
    rows = jnp.asarray(
        rng.permutation(pool)[: b * ctx].reshape(b, ctx), jnp.int32
    )
    lengths = jnp.asarray(lengths, jnp.int32)
    counts = jnp.asarray(counts, jnp.int32)
    scales = {}
    if int8:
        kp, ks = ref.quantize_kv(kp)
        vp, vs = ref.quantize_kv(vp)
        scales = dict(k_scale=ks, v_scale=vs)
    return q, kp, vp, rows, lengths, counts, scales


@pytest.mark.parametrize("k", [1, 2, 4, 8])
@pytest.mark.parametrize("gqa", [1, 2, 4])
@pytest.mark.parametrize("int8", [False, True])
def test_matches_ref_sweep(k, gqa, int8):
    """K × GQA × dtype sweep over ragged verify chunks: per-row live
    lengths differ by pages, one row starts mid-page and straddles a page
    boundary, one lands exactly on a boundary, and a ``counts == 0``
    padding row stays all-zero (the capacity-clamp stall case)."""
    rng = np.random.default_rng(100 + k + 10 * gqa + 100 * int8)
    h, kvh, d, page, ctx = 4, 4 // gqa, 16, 4, 6
    lengths = [0, 3, 8, 13]               # fresh, mid-page, exact, straddle
    counts = [k, k, k, 0]
    q, kp, vp, rows, lens, cnts, scales = _verify_case(
        rng, b=4, k=k, h=h, kvh=kvh, d=d, pool=32, page=page, ctx=ctx,
        lengths=lengths, counts=counts, int8=int8,
    )
    want = ops.paged_verify(q, kp, vp, rows, lens, cnts, impl="ref",
                            **scales)
    got = ops.paged_verify(q, kp, vp, rows, lens, cnts, impl="pallas",
                           **scales)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got)[3]).max() == 0.0   # stalled row → zeros


def test_verify_is_prefill_at_the_tail():
    """The defining identity: a verify chunk over live length L IS a
    prefill chunk with ``starts = L`` — same oracle, same kernel, bit for
    bit (the engine's bit-exactness is by construction, not coincidence)."""
    rng = np.random.default_rng(9)
    q, kp, vp, rows, lens, cnts, _ = _verify_case(
        rng, b=3, k=4, h=4, kvh=2, d=16, pool=24, page=4, ctx=5,
        lengths=[2, 7, 12], counts=[4, 4, 3],
    )
    via_verify = ops.paged_verify(q, kp, vp, rows, lens, cnts, impl="ref")
    via_prefill = ops.paged_prefill_attention(
        q, kp, vp, rows, lens, cnts, impl="ref"
    )
    np.testing.assert_array_equal(np.asarray(via_verify),
                                  np.asarray(via_prefill))


# ---------------------------------------------------------------------------
# Acceptance rule
# ---------------------------------------------------------------------------


def _accept_oracle(drafts, greedy, counts):
    """Python re-statement of the greedy accept rule: the longest draft
    prefix matching the model's own argmax, plus one bonus token, capped
    by the scored count."""
    b, km1 = drafts.shape
    out = np.zeros((b,), np.int32)
    for i in range(b):
        a = 0
        while a < km1 and drafts[i, a] == greedy[i, a]:
            a += 1
        out[i] = min(a + 1, counts[i])
    return out


@pytest.mark.parametrize("k", [1, 2, 4, 8])
def test_speculative_accept_matches_python_oracle(k):
    rng = np.random.default_rng(k)
    b = 16
    drafts = rng.integers(0, 3, (b, k - 1)).astype(np.int32)
    greedy = rng.integers(0, 3, (b, k)).astype(np.int32)
    counts = rng.integers(0, k + 1, (b,)).astype(np.int32)
    got = np.asarray(ops.speculative_accept(
        jnp.asarray(drafts), jnp.asarray(greedy), jnp.asarray(counts)
    ))
    np.testing.assert_array_equal(got, _accept_oracle(drafts, greedy, counts))


def test_speculative_accept_truncates_at_first_mismatch():
    """Tokens after the first mismatch never count, even if they match."""
    drafts = jnp.asarray([[5, 9, 7]], jnp.int32)
    greedy = jnp.asarray([[5, 1, 7, 3]], jnp.int32)   # mismatch at column 1
    n = ops.speculative_accept(drafts, greedy, jnp.asarray([4], jnp.int32))
    assert int(n[0]) == 2                              # matched prefix + bonus
    # All match → everything plus the bonus token.
    n = ops.speculative_accept(
        drafts, jnp.asarray([[5, 9, 7, 3]], jnp.int32),
        jnp.asarray([4], jnp.int32),
    )
    assert int(n[0]) == 4
    # Clamp: capacity caps the emission below the matched prefix.
    n = ops.speculative_accept(
        drafts, jnp.asarray([[5, 9, 7, 3]], jnp.int32),
        jnp.asarray([2], jnp.int32),
    )
    assert int(n[0]) == 2


def test_speculative_accept_k1_degenerates_to_plain_decode():
    """K=1: zero drafts, so every active row emits exactly its bonus token
    — the plain decode step in speculative clothing."""
    drafts = jnp.zeros((3, 0), jnp.int32)
    greedy = jnp.asarray([[4], [2], [9]], jnp.int32)
    counts = jnp.asarray([1, 1, 0], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(ops.speculative_accept(drafts, greedy, counts)),
        [1, 1, 0],
    )


# ---------------------------------------------------------------------------
# Engine: verify_upto ≡ decode_upto, bit for bit
# ---------------------------------------------------------------------------


def _engine(spec_k, kv_dtype=None, drafter=None, seed=3):
    model = _sharpen(PagedLM(CFG, jax.random.PRNGKey(seed), impl="ref",
                             spec_k=spec_k, kv_dtype=kv_dtype,
                             drafter=drafter))
    cache = PagedKVCache.create(CFG, batch=2, max_len=64, page=8,
                                kv_dtype=kv_dtype)
    prompts = [np.arange(1, 6, dtype=np.int32) % CFG.vocab,
               (np.arange(11, 23, dtype=np.int32) * 7) % CFG.vocab]
    feed = np.zeros((2,), np.int32)
    for s, p in enumerate(prompts):
        cache = cache.allocate(s, cache.pages_for(64))
        for start in range(0, len(p), 8):
            cnt = min(8, len(p) - start)
            buf = np.zeros((8,), np.int32)
            buf[:cnt] = p[start:start + cnt]
            logits, cache = model.prefill_chunk(
                jnp.asarray(buf), cnt, s, start, cache
            )
        feed[s] = int(np.argmax(np.asarray(logits)[: CFG.vocab]))
    return model, cache, feed


def _spec_tokens(model, cache, feed, n_steps, total):
    """Flatten a verify_upto run's emissions per slot, first ``total``."""
    active = np.ones((2,), bool)
    dstate = model.drafter.init_state(2)
    toks, counts, cache, _ = model.verify_upto(
        feed, cache, active, n_steps, dstate
    )
    out = []
    for s in range(2):
        flat = []
        for step in range(toks.shape[0]):
            flat.extend(int(t) for t in toks[step, s, : counts[step, s]])
        out.append(flat[:total])
    return out


@pytest.mark.parametrize("spec_k", [1, 2, 4, 8])
@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_engine_emits_plain_greedy_sequence(spec_k, kv_dtype):
    """verify_upto's emitted stream equals decode_upto's, bitwise, for
    every K and pool dtype — first tokens of the two feeds included."""
    total = 16
    model, cache, feed = _engine(1, kv_dtype)
    plain, _ = model.decode_upto(feed, cache, np.ones((2,), bool), total)
    want = [[int(t) for t in plain[:, s]] for s in range(2)]

    model, cache, feed = _engine(spec_k, kv_dtype)
    # Enough steps to emit ``total`` even at the 1-token-per-step floor.
    got = _spec_tokens(model, cache, feed, total, total)
    assert got == want


def test_engine_equivalence_is_drafter_independent():
    """A different drafter changes acceptance, never bits: the n-gram and
    tiny-LM drafters emit identical streams (the correctness/performance
    separation the replay story depends on)."""
    total = 12
    draft_embed = _sharpen(
        PagedLM(CFG, jax.random.PRNGKey(7), impl="ref")
    ).params["embed"]
    outs = []
    for drafter in (None, TinyLMDrafter(draft_embed, vocab=CFG.vocab)):
        model, cache, feed = _engine(4, drafter=drafter)
        outs.append(_spec_tokens(model, cache, feed, total, total))
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# Scheduler: spec_k > 1 ≡ static batch, including chaos replay
# ---------------------------------------------------------------------------


def _sched_model(spec_k, seed=3):
    return _sharpen(PagedLM(CFG, jax.random.PRNGKey(seed), impl="ref",
                            spec_k=spec_k))


def _sched_prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(0, CFG.vocab, n).astype(np.int32)
            for n in (5, 12, 9)]


@pytest.mark.parametrize("spec_k", [2, 4])
def test_scheduler_matches_static_batch(spec_k):
    prompts = _sched_prompts()
    max_new = 12
    want = static_batch_generate(
        _sched_model(1),
        PagedKVCache.create(CFG, batch=4, max_len=64, page=8),
        prompts, max_new, chunk=8,
    )
    cache = PagedKVCache.create(CFG, batch=4, max_len=64, page=8)
    sched = Scheduler(_sched_model(spec_k), cache, chunk=8)
    for i, p in enumerate(prompts):
        sched.submit(Request(rid=i, prompt=p, max_new=max_new))
    out = sched.run()
    assert out == want
    st = sched.stats
    assert st.spec_steps > 0
    assert st.n_drafted > 0 and st.n_accepted > 0
    assert st.n_emitted == sum(len(v) for v in out.values()) - len(out)
    assert 0.0 < st.acceptance_rate <= 1.0
    # Every verify step is a decode step, and every token counts once.
    assert st.decode_steps == st.spec_steps
    assert st.tokens == sum(len(v) for v in out.values())


def test_scheduler_spec_k1_is_plain_decode_path():
    """spec_k=1 never calls the verify path: records and outputs are the
    plain fused-decode ones (kind='decode' only, zero draft accounting)."""
    prompts = _sched_prompts()
    cache = PagedKVCache.create(CFG, batch=4, max_len=64, page=8)
    sched = Scheduler(_sched_model(1), cache, chunk=8)
    for i, p in enumerate(prompts):
        sched.submit(Request(rid=i, prompt=p, max_new=8))
    out = sched.run()
    assert sched.stats.spec_steps == 0
    assert sched.stats.n_drafted == 0
    assert all(len(v) == 8 for v in out.values())


def test_eviction_mid_speculation_replays_bit_for_bit():
    """A pool too small for all residents forces evictions between verify
    launches; replay re-prefills and re-feeds through the same speculative
    path and must reproduce the unconstrained outputs exactly.  Replay
    charges only accepted (emitted) tokens: replay_spent counts
    prompt + generated, never the rejected drafts."""
    prompts = _sched_prompts()
    max_new = 14
    roomy, _ = _run_sched(4, prompts, max_new, pool_pages=None)
    tight, sched = _run_sched(4, prompts, max_new, pool_pages=6)
    assert tight == roomy
    assert sched.stats.n_evictions > 0
    for r in list(sched.finished.values()):
        assert r.replay_spent <= r.n_evictions * (r.prompt_len + max_new)


def _run_sched(spec_k, prompts, max_new, pool_pages=None, faults=None):
    kw = {} if pool_pages is None else dict(pool_pages=pool_pages)
    cache = PagedKVCache.create(CFG, batch=4, max_len=64, page=8, **kw)
    sched = Scheduler(_sched_model(spec_k), cache, chunk=8, faults=faults)
    reqs = [Request(rid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        sched.submit(r)
    if faults is not None:
        while sched.queue or sched.resident:
            sched.step()
            check_scheduler_invariants(sched, reqs)
        out = {rid: r.generated for rid, r in sorted(sched.finished.items())}
    else:
        out = sched.run()
    return out, sched


def test_chaos_faults_with_speculation():
    """The chaos seed case: injected exhaustion/denial during speculative
    serving degrades through the same ladder and stays bit-for-bit."""
    prompts = _sched_prompts()
    want, _ = _run_sched(1, prompts, 12)
    plan = FaultPlan.random(200, n_steps=30)
    got, sched = _run_sched(4, prompts, 12, pool_pages=10, faults=plan)
    assert got == want
    sched.family.check_integrity()


# ---------------------------------------------------------------------------
# Jit-program LRU: verify buckets share the prefill cache
# ---------------------------------------------------------------------------


def test_verify_jits_share_bounded_lru():
    """Verify programs are keyed ('verify', spec_k, page, ctx) in the *same*
    bounded LRU as the (page, ctx) prefill buckets: a (page × launch-width)
    sweep mints prefill and verify keys past the cap, the cache never
    exceeds it, and an evicted verify bucket transparently re-jits with
    identical emitted tokens."""
    model = _sched_model(4)
    model.prefill_cache_cap = 3
    prompts = [np.arange(1, 6, dtype=np.int32) % CFG.vocab,
               (np.arange(11, 23, dtype=np.int32) * 7) % CFG.vocab]

    def spec_run(page, n_steps):
        cache = PagedKVCache.create(CFG, batch=2, max_len=64, page=page)
        feed = np.zeros((2,), np.int32)
        for s, p in enumerate(prompts):
            cache = cache.allocate(s, cache.pages_for(64))
            logits, cache = model.prefill_chunk(
                jnp.asarray(p), len(p), s, 0, cache
            )
            feed[s] = int(np.argmax(np.asarray(logits)[: CFG.vocab]))
        return _spec_tokens(model, cache, feed, n_steps, 4)

    keys_seen = set()
    outs = {}
    for combo in ((4, 1), (4, 8), (8, 1), (8, 8)):
        outs[combo] = spec_run(*combo)
        keys_seen |= set(model._prefill_cache)
        assert len(model._prefill_cache) <= 3       # cap always holds
    verify_keys = {k for k in keys_seen if k[0] == "verify"}
    prefill_keys = keys_seen - verify_keys
    assert verify_keys and prefill_keys             # both kinds share the LRU
    assert all(k[1] == 4 for k in verify_keys)      # keyed by spec_k
    assert len(keys_seen) > 3                       # sweep minted past cap
    assert set(model._prefill_cache) < keys_seen    # something was evicted
    # Re-running the first (now evicted) bucket re-jits and reproduces its
    # emitted tokens exactly.
    assert spec_run(4, 1) == outs[(4, 1)]
    assert len(model._prefill_cache) <= 3
