"""Continuous-batching scheduler tests: page-pool admission control,
eviction/re-admission round-trips, bit-for-bit equivalence between
scheduled continuous batching and a single static batch, and the
scheduler's counters."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.kernels import ops, ref
from repro.serve import (
    OutOfPages,
    PagedKVCache,
    PagedLM,
    RecurrentLM,
    RejectReason,
    Request,
    RequestRejected,
    RequestState,
    Scheduler,
    SchedulerStalledError,
    ServeStats,
    static_batch_generate,
)

CFG = smoke_config("yi-6b")
MODEL = PagedLM(CFG, jax.random.PRNGKey(0), impl="ref")


def _prompts(rng, lens):
    return [rng.integers(0, CFG.vocab, n).astype(np.int32) for n in lens]


# ---------------------------------------------------------------------------
# Admission control
# ---------------------------------------------------------------------------


def test_admission_blocks_when_pool_full():
    rng = np.random.default_rng(0)
    prompts = _prompts(rng, (8, 8))
    # Pool of 3 pages: each request peaks at 3 → only one resident at once.
    cache = PagedKVCache.create(CFG, batch=2, max_len=12, page=4, pool_pages=3)
    sched = Scheduler(MODEL, cache, chunk=4)
    reqs = [Request(rid=i, prompt=p, max_new=2) for i, p in enumerate(prompts)]
    for r in reqs:
        sched.submit(r)
    sched.step()
    assert reqs[0].state in (RequestState.PREFILL, RequestState.RUNNING)
    assert reqs[1].state is RequestState.WAITING  # pool-full: not admitted
    out = sched.run()
    assert sorted(out) == [0, 1]
    assert all(len(t) == 2 for t in out.values())
    assert sched.cache.n_free == 3  # all pages returned


def test_submit_rejects_request_larger_than_pool():
    cache = PagedKVCache.create(CFG, batch=1, max_len=8, page=4, pool_pages=1)
    sched = Scheduler(MODEL, cache, chunk=4)
    req = Request(rid=0, prompt=np.zeros(8, np.int32), max_new=4)
    with pytest.raises(RequestRejected) as exc:
        sched.submit(req)
    # Typed, non-fatal rejection: the request is terminal, not lost, and the
    # scheduler stays usable.
    assert exc.value.reason is RejectReason.NEVER_FITS
    assert req.state is RequestState.REJECTED
    assert sched.rejected[0] is req
    assert sched.stats.n_rejected == 1
    # Non-strict submission reports rather than raises.
    req2 = Request(rid=1, prompt=np.zeros(8, np.int32), max_new=4)
    assert sched.submit(req2, strict=False) is False
    assert req2.reject_reason is RejectReason.NEVER_FITS


def test_submit_rejects_infeasible_deadline():
    cache = PagedKVCache.create(CFG, batch=2, max_len=16, page=4)
    sched = Scheduler(MODEL, cache, chunk=4)
    # 8-token prompt at chunk=4 needs 2 prefill steps + 1 decode boundary.
    req = Request(rid=0, prompt=np.zeros(8, np.int32), max_new=4,
                  deadline_steps=2)
    assert sched.submit(req, strict=False) is False
    assert req.reject_reason is RejectReason.DEADLINE_INFEASIBLE
    assert sched.stats.deadline_misses == 1
    # The same request with a feasible deadline is served.
    ok = Request(rid=1, prompt=np.zeros(8, np.int32), max_new=4,
                 deadline_steps=8)
    assert sched.submit(ok) is True
    sched.run()
    assert len(sched.finished[1].generated) == 4
    assert sched.stats.deadline_misses == 1  # met: no new miss


def test_stall_diagnostic_names_stuck_request():
    cache = PagedKVCache.create(CFG, batch=2, max_len=32, page=4)
    sched = Scheduler(MODEL, cache, chunk=4)
    sched.submit(Request(rid=7, prompt=np.zeros(16, np.int32), max_new=8))
    with pytest.raises(SchedulerStalledError) as exc:
        sched.run(max_steps=1)  # prefill alone needs 4 steps
    msg = str(exc.value)
    assert "request 7" in msg
    assert "queued" in msg and "pages free" in msg
    assert "prefill_pos=4/16" in msg


# ---------------------------------------------------------------------------
# Eviction / re-admission
# ---------------------------------------------------------------------------


def test_eviction_readmission_roundtrip():
    rng = np.random.default_rng(1)
    prompts = _prompts(rng, (8, 7))
    max_new = 8

    cache_ref = PagedKVCache.create(CFG, batch=2, max_len=16, page=4)
    want = static_batch_generate(MODEL, cache_ref, prompts, max_new, chunk=4)

    # 6-page pool, both requests growing to 4 pages → mid-decode contention.
    cache = PagedKVCache.create(CFG, batch=2, max_len=16, page=4, pool_pages=6)
    sched = Scheduler(MODEL, cache, chunk=4)
    reqs = [Request(rid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        sched.submit(r)
    got = sched.run()

    assert sched.stats.n_evictions >= 1
    assert max(r.n_evictions for r in reqs) >= 1
    assert got == {i: want[i] for i in want}  # eviction invisible in output
    assert sched.cache.n_free == 6


def test_eviction_prefers_youngest_and_self_defers():
    """When the page-needing request is itself the youngest resident, it
    defers rather than evicting an older (possibly nearly-done) request."""
    rng = np.random.default_rng(5)
    prompts = _prompts(rng, (4, 4))
    max_new = 12

    cache_ref = PagedKVCache.create(CFG, batch=2, max_len=16, page=4)
    want = static_batch_generate(MODEL, cache_ref, prompts, max_new, chunk=4)

    # 5-page pool; both requests peak at 4 pages → the younger one must
    # yield when both cross the 8-token page boundary.
    cache = PagedKVCache.create(CFG, batch=2, max_len=16, page=4, pool_pages=5)
    sched = Scheduler(MODEL, cache, chunk=4)
    reqs = [Request(rid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        sched.submit(r)
    got = sched.run()

    assert reqs[0].n_evictions == 0      # the elder is never preempted
    assert reqs[1].n_evictions >= 1      # the younger defers itself
    assert got == {i: want[i] for i in want}


def test_submit_rejects_nonpositive_max_new():
    cache = PagedKVCache.create(CFG, batch=1, max_len=8, page=4)
    sched = Scheduler(MODEL, cache, chunk=4)
    with pytest.raises(ValueError):
        sched.submit(Request(rid=0, prompt=np.zeros(4, np.int32), max_new=0))


# ---------------------------------------------------------------------------
# Scheduled continuous batching ≡ static batch (the acceptance criterion)
# ---------------------------------------------------------------------------


def test_scheduled_equals_static_batch():
    rng = np.random.default_rng(2)
    prompts = _prompts(rng, (5, 9, 12))
    max_new = 6

    cache_ref = PagedKVCache.create(CFG, batch=3, max_len=32, page=4)
    want = static_batch_generate(MODEL, cache_ref, prompts, max_new, chunk=4)

    # Tight pool staggers admission; chunked prefill interleaves with decode.
    cache = PagedKVCache.create(CFG, batch=3, max_len=32, page=4,
                                pool_pages=10)
    sched = Scheduler(MODEL, cache, chunk=4)
    streamed, finished = [], []
    for i, p in enumerate(prompts):
        sched.submit(Request(
            rid=i, prompt=p, max_new=max_new,
            on_token=lambda r, t: streamed.append((r.rid, t)),
            on_finish=lambda r: finished.append(r.rid),
        ))
    got = sched.run()

    assert got == {i: want[i] for i in want}  # bit-for-bit token equality
    assert sorted(finished) == [0, 1, 2]
    # Streaming hooks: every token exactly once, in generation order per rid.
    for i in range(3):
        assert [t for rid, t in streamed if rid == i] == got[i]
    # Counters: every token once; the longest prompt needs 3 prefill
    # chunks, and each request max_new - 1 decode steps.
    st = sched.stats
    assert st.tokens == 3 * max_new
    assert st.prefill_steps >= 3
    assert st.decode_steps >= max_new - 1
    assert st.spec_steps == st.share_events == st.shared_pages == 0


# ---------------------------------------------------------------------------
# Paged KV append op (the indirect write converter in serving)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_paged_kv_append_writes_one_row_per_active_seq(impl):
    rng = np.random.default_rng(3)
    p_tot, page, kvh, d, b = 6, 4, 2, 16, 3
    kp = jnp.asarray(rng.normal(size=(p_tot, page, kvh, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(p_tot, page, kvh, d)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(b, kvh, d)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(b, kvh, d)), jnp.float32)
    table = jnp.asarray([[0, 1], [2, 3], [4, 5]], jnp.int32)
    lengths = jnp.asarray([5, 3, 0], jnp.int32)
    active = jnp.asarray([True, True, False])

    k2, v2, l2 = ops.paged_kv_append(kp, vp, kn, vn, table, lengths, active,
                                     impl=impl)
    np.testing.assert_array_equal(np.asarray(l2), [6, 4, 0])
    # seq 0 wrote to page 1 offset 1; seq 1 to page 2 offset 3; seq 2 nothing.
    np.testing.assert_allclose(np.asarray(k2[1, 1]), np.asarray(kn[0]))
    np.testing.assert_allclose(np.asarray(v2[2, 3]), np.asarray(vn[1]))
    expect = np.asarray(kp).copy()
    expect[1, 1] = np.asarray(kn[0])
    expect[2, 3] = np.asarray(kn[1])
    np.testing.assert_allclose(np.asarray(k2), expect)


def test_paged_kv_append_pallas_matches_ref():
    rng = np.random.default_rng(4)
    p_tot, page, kvh, d, b = 8, 4, 2, 16, 5
    kp = jnp.asarray(rng.normal(size=(p_tot, page, kvh, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(p_tot, page, kvh, d)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(b, kvh, d)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(b, kvh, d)), jnp.float32)
    table = jnp.asarray(rng.permutation(p_tot)[: b * 1].reshape(b, 1),
                        jnp.int32)
    lengths = jnp.asarray(rng.integers(0, page, b), jnp.int32)
    active = jnp.asarray([True, False, True, True, False])
    outs = [
        ops.paged_kv_append(kp, vp, kn, vn, table, lengths, active, impl=im)
        for im in ("ref", "pallas")
    ]
    for a, b_ in zip(outs[0], outs[1]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


# ---------------------------------------------------------------------------
# Cache bookkeeping under mid-flight entry/exit
# ---------------------------------------------------------------------------


def test_paged_cache_midflight_extend_and_release():
    cache = PagedKVCache.create(CFG, batch=2, max_len=32, page=4,
                                pool_pages=8)
    cache = cache.allocate(0, 2)
    cache = cache.allocate(1, 3)
    assert cache.n_free == 3
    cache = cache.allocate(0, 1)  # mid-flight growth appends, not overwrites
    table = np.asarray(cache.page_table)
    assert len(set(table[0, :3].tolist())) == 3
    assert cache.n_free == 2
    with pytest.raises(OutOfPages):
        cache.allocate(0, 3)
    cache = cache.release(1)
    assert cache.n_free == 5
    assert int(np.asarray(cache.lengths)[1]) == 0
    cache = cache.release(0)
    assert sorted(cache.free) == list(range(8))


# ---------------------------------------------------------------------------
# ServeStats: fixed-size counters that agree with the launches
# ---------------------------------------------------------------------------


def _stats_scheduler(case):
    """A scheduler for one ``test_serve_stats_counters`` case."""
    if case in ("rwkv6", "mamba"):
        cfg = smoke_config("rwkv6-3b" if case == "rwkv6" else "yi-6b")
        model = RecurrentLM(cfg, jax.random.PRNGKey(0), arch=case)
        return cfg, Scheduler(model, model.init_pool(2), chunk=4)
    kv = "int8" if case == "int8" else None
    model = PagedLM(CFG, jax.random.PRNGKey(0), impl="ref", kv_dtype=kv,
                    spec_k=4 if case == "spec4" else 1)
    cache = PagedKVCache.create(CFG, batch=2, max_len=32, page=4,
                                kv_dtype=kv)
    return CFG, Scheduler(model, cache, chunk=4,
                          prefix_sharing=case == "sharing")


def _launch_spans(tmp_path, fn):
    """Run ``fn`` inside a profiler window; returns the stats of its
    ``engine.launch`` spans."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    return [dict(e.stats) for pl in ProfileData.from_file(path).planes
            if pl.name == "/host:CPU" for ln in pl.lines for e in ln.events
            if e.name == "engine.launch"]


def _stored(stats: ServeStats) -> int:
    """Values ``stats`` holds: one per counter, one per reject reason."""
    return sum(len(v) if isinstance(v, dict) else 1
               for v in vars(stats).values())


@pytest.mark.parametrize(
    "case", ["fp32", "int8", "spec4", "sharing", "rwkv6", "mamba"])
def test_serve_stats_counters(tmp_path, case):
    """Requests arrive for 100 steps and then drain.  The counters equal
    what the launches and the requests show, and ``ServeStats`` holds as
    many values after 100 steps as after 10."""
    cfg, sched = _stats_scheduler(case)
    rng = np.random.default_rng(11)
    system = rng.integers(0, cfg.vocab, 8)
    reqs = []
    sizes = {}

    def serve():
        while sched._step < 100:
            if len(sched.queue) < 2:
                tail = rng.integers(0, cfg.vocab, int(rng.integers(1, 6)))
                prompt = np.concatenate([system, tail]).astype(np.int32)
                reqs.append(Request(rid=len(reqs), prompt=prompt,
                                    max_new=int(rng.integers(2, 9))))
                sched.submit(reqs[-1])
            sched.step()
            if sched._step in (10, 100):
                sizes[sched._step] = _stored(sched.stats)
        sched.run()

    launches = _launch_spans(tmp_path, serve)
    st = sched.stats
    assert sizes[10] == sizes[100]
    assert all(isinstance(v, int) for k, v in vars(st).items()
               if k != "reject_reasons")
    assert len(sched.finished) == len(reqs) > 10

    def launched(*kinds):
        return [sp for sp in launches if sp["kind"] in kinds]

    assert st.prefill_steps == len(launched("prefill")) > 0
    assert st.decode_steps == sum(sp["n"]
                                  for sp in launched("decode", "verify"))
    assert st.spec_steps == sum(sp["n"] for sp in launched("verify"))
    assert st.tokens == sum(len(r.generated) for r in reqs)
    assert (st.spec_steps > 0) == (case == "spec4")
    # Every request's first token comes from its prefill; verify emits
    # the rest.
    assert st.n_emitted == (st.tokens - len(reqs) if case == "spec4" else 0)
    if case == "sharing":
        assert st.share_events > 0
        assert 0 < st.prefill_tokens_saved <= 4 * st.shared_pages
    else:
        assert st.share_events == st.shared_pages == 0
