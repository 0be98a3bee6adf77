"""Serving benchmark: continuous-batching decode throughput over paged
AXI-Pack streams.

For each batch size B, submits B variable-length requests to the
:class:`repro.serve.Scheduler` and measures end-to-end decode throughput
plus the per-step BASE-vs-PACK bus traffic (the serving-side instance of the
Fig. 3 accounting: BASE streams the padded contiguous cache, PACK streams
only mapped pages plus the near-memory page-table fetch).  A separate timed
phase measures batched *prefill* throughput in isolation (the scheduler's
``prefill_batch`` calls without decode interleaved), alongside the
prefill-side PACK/BASE efficiencies aggregated from the scheduler's
per-step records.

The same sweep re-runs with ``kv_dtype='int8'`` (the ``serving_int8``
section): quantize-on-write page pools, in-kernel dequant, and the 8-bit
packing factor in the PACK accounting — pool bytes quartered vs fp32 and
4x the elements per bus granule, the paper's element-size lever (§III-E)
applied to serving.

The ``serving_shared_prefix`` section measures prefix sharing: batches
whose prompts repeat one page-aligned system prompt run once with
``prefix_sharing=True`` and once without, asserting bit-for-bit identical
outputs, and report the fraction of prompt tokens whose prefill was
replaced by a refcount bump plus the effective prefill PACK efficiency
(shared tokens cost only the remapped table indices — the Ferry-style
dedup-before-packing multiplier on the serving path).

The ``serving_families`` section serves *recurrent* models (RWKV6, Mamba)
through the very same scheduler via the :class:`repro.serve.ServableFamily`
protocol: fixed-size state slots instead of growing page chains, and
strided-burst PACK/BASE accounting (no index-bus term — the stride is the
descriptor) instead of indirect page walks.  Each row asserts the scheduled
tokens equal the direct forward fed the same prompt chunks
(:func:`repro.serve.recurrent_reference_generate` at the scheduler's chunk)
before reporting throughput, so the benchmark doubles as the family
protocol's end-to-end correctness gate.

The measured run is steady-state: the warmup pass executes the *same*
workload so every jit entry the fused decode fast path uses (pow2 scan
lengths, prefill context buckets) is compiled before the clock starts, and
the reported wall time is the best of ``repeats`` timed runs (scheduler
wall-clock is tens of ms here, well inside host-noise territory).
Wall-clock numbers are CPU ``impl='ref'`` timings — regression signal for
this host, not TPU predictions (the roofline section covers the target).
The traffic columns are exact byte counts and *are* paper-comparable.
"""
from __future__ import annotations

import time
from typing import Dict, List, Sequence

import jax
import numpy as np

from repro.configs import smoke_config
from repro.serve import (
    FaultPlan,
    PagedKVCache,
    PagedLM,
    RecurrentLM,
    Request,
    Scheduler,
    build_prefill_rows,
    recurrent_reference_generate,
)

PAGE = 8
MAX_LEN = 64
CHUNK = 8


def _create_cache(model: PagedLM, batch: int) -> PagedKVCache:
    # Pools at the model's exact kv dtype: the Scheduler rejects mismatches.
    return PagedKVCache.create(
        model.cfg, batch=batch, max_len=MAX_LEN, page=PAGE,
        kv_dtype=model.kv_dtype,
    )


def _run_once(model: PagedLM, prompts, n_new: int) -> Scheduler:
    cache = _create_cache(model, len(prompts))
    sched = Scheduler(model, cache, chunk=CHUNK)
    for i, p in enumerate(prompts):
        sched.submit(Request(rid=i, prompt=p, max_new=n_new))
    sched.run()
    return sched


def _prefill_once(model: PagedLM, prompts) -> float:
    """One batched chunked prefill of every prompt (the scheduler's prefill
    phase in isolation: same ``prefill_batch`` calls, no decode).

    Batch assembly goes through the scheduler's own
    :func:`repro.serve.build_prefill_rows` (finished prompts drop out,
    pow2-bucketed rows), so the timed work is exactly what
    ``Scheduler._prefill_all`` issues.  Returns the wall seconds of the
    prefill loop only — cache creation and page allocation happen before
    the clock starts (the pools are donated, so the cache must be rebuilt
    per repeat, but that setup is host bookkeeping, not prefill).
    """
    b = len(prompts)
    cache = _create_cache(model, b)
    for i, p in enumerate(prompts):
        cache = cache.allocate(i, cache.pages_for(len(p)))
    pos = [0] * b
    pending = list(range(b))
    logits = None
    t0 = time.perf_counter()
    while pending:
        toks, counts, slots, starts = build_prefill_rows(
            [(prompts[j], pos[j], j) for j in pending], CHUNK, b
        )
        logits, cache = model.prefill_batch(toks, counts, slots, starts, cache)
        for i, j in enumerate(pending):
            pos[j] += int(counts[i])
        pending = [j for j in pending if pos[j] < len(prompts[j])]
    jax.block_until_ready(logits)
    return time.perf_counter() - t0


def _prefill_throughput(model: PagedLM, prompts, repeats: int) -> float:
    """Prompt tokens/s of the batched prefill phase (best of ``repeats``)."""
    tokens = sum(len(p) for p in prompts)
    _prefill_once(model, prompts)  # warmup: compile the ctx buckets
    wall = min(_prefill_once(model, prompts) for _ in range(max(1, repeats)))
    return tokens / wall


def shared_prefix_rows(
    batch_sizes: Sequence[int] = (2, 4, 8),
    n_new: int = 8,
    sys_tokens: int = 32,
    quick: bool = False,
    repeats: int = 3,
) -> List[Dict]:
    """Prefix-sharing sweep: every prompt in a batch repeats one
    page-aligned ``sys_tokens``-token system prompt with a distinct short
    tail.  Each batch runs through a sharing and a non-sharing scheduler
    (fresh caches, identical submissions) and the row asserts the outputs
    are bit-for-bit equal before reporting the savings — a benchmark that
    fails loudly if the replay contract breaks.
    """
    if quick:
        batch_sizes = (2, 4)
    assert sys_tokens % PAGE == 0, "system prompt must be page-aligned"
    cfg = smoke_config("yi-6b")
    model = PagedLM(cfg, jax.random.PRNGKey(0), impl="ref")
    rng = np.random.default_rng(7)
    rows = []
    for b in batch_sizes:
        sys_prompt = rng.integers(0, cfg.vocab, sys_tokens)
        prompts = [
            np.concatenate(
                [sys_prompt, rng.integers(0, cfg.vocab, int(t))]
            ).astype(np.int32)
            for t in rng.integers(4, 9, b)
        ]

        def _run(sharing: bool) -> Scheduler:
            cache = _create_cache(model, b)
            sched = Scheduler(model, cache, chunk=CHUNK,
                              prefix_sharing=sharing)
            for i, p in enumerate(prompts):
                sched.submit(Request(rid=i, prompt=p, max_new=n_new))
            sched.run()
            return sched

        for sharing in (True, False):
            _run(sharing)               # warmup: compile all jit entries
        wall = {True: float("inf"), False: float("inf")}
        for _ in range(max(1, repeats)):
            for sharing in (True, False):
                t0 = time.perf_counter()
                sched = _run(sharing)
                wall[sharing] = min(wall[sharing], time.perf_counter() - t0)
                if sharing:
                    shared_sched = sched
                else:
                    plain_sched = sched
        out_s = {r: shared_sched.finished[r].generated
                 for r in shared_sched.finished}
        out_p = {r: plain_sched.finished[r].generated
                 for r in plain_sched.finished}
        assert out_s == out_p, "prefix sharing changed outputs"
        st = shared_sched.stats
        prompt_tokens = sum(len(p) for p in prompts)
        rows.append({
            "batch": b,
            "prompt_tokens": prompt_tokens,
            "prefill_tokens_saved": st.prefill_tokens_saved,
            "saved_frac": st.prefill_tokens_saved / prompt_tokens,
            "shared_pages": st.shared_pages,
            "share_events": st.share_events,
            "cow_copies": st.cow_copies,
            "prefill_pack_eff": st.prefill_pack_efficiency,
            "effective_pack_eff": st.prefill_effective_pack_efficiency,
            "plain_pack_eff": plain_sched.stats.prefill_pack_efficiency,
            "wall_s": wall[True],
            "wall_s_plain": wall[False],
            "tokens_per_s": st.tokens / wall[True],
            "outputs_match": True,
        })
    return rows


def degradation_rows(
    n_reqs: int = 6,
    n_new: int = 8,
    quick: bool = False,
    fractions: Sequence[float] = (1.0, 0.5, 0.25, 0.12),
) -> List[Dict]:
    """Throughput under pool pressure: the robustness/degradation sweep.

    A fixed mixed-SLA workload (alternating priorities, deadlines on the
    interactive half, replay budgets on every third request) runs against
    pools shrunk to a fraction of the roomy full-pool footprint, plus one
    row with a seeded :class:`repro.serve.FaultPlan` injecting forced
    exhaustion / denied allocations on top of a halved pool.  Every row
    records the degradation counters (`evictions`, `preemptions`,
    `rejections`, `deadline_misses`) next to tokens/s, and asserts the
    liveness + correctness contract: **all requests terminal** (no
    deadlock, no crash) and **finished outputs bit-for-bit equal** to the
    full-pool fault-free reference.  CI fails the BENCH artifact if either
    flag is False.
    """
    if quick:
        fractions = (1.0, 0.5, 0.12)
    cfg = smoke_config("yi-6b")
    model = PagedLM(cfg, jax.random.PRNGKey(0), impl="ref")
    rng = np.random.default_rng(3)
    lens = rng.integers(4, 25, n_reqs)
    prompts = [rng.integers(0, cfg.vocab, int(n)).astype(np.int32)
               for n in lens]
    # Roomy footprint: every request fully grown at once.
    full = sum(-(-(len(p) + n_new - 1) // PAGE) for p in prompts)
    batch = min(n_reqs, 3)  # fewer slots than requests: real queueing

    def make_requests():
        return [
            Request(
                rid=i, prompt=p.copy(), max_new=n_new,
                priority=i % 2,
                deadline_steps=40 if i % 2 else None,
                replay_budget=(2 * (len(p) + n_new) if i % 3 == 0 else None),
            )
            for i, p in enumerate(prompts)
        ]

    def run(pool: int, faults) -> Scheduler:
        cache = PagedKVCache.create(
            cfg, batch=batch, max_len=MAX_LEN, page=PAGE, pool_pages=pool,
        )
        sched = Scheduler(model, cache, chunk=CHUNK, faults=faults)
        reqs = make_requests()
        for r in reqs:
            sched.submit(r, strict=False)
        sched.run(max_steps=2000)
        return sched

    run(full, None)  # warmup: compile every jit entry on the same workload
    reference = {
        rid: r.generated for rid, r in run(full, None).finished.items()
    }

    cases = [(f"pool×{f:g}", f, None) for f in fractions]
    cases.append(
        ("chaos pool×0.5", 0.5,
         FaultPlan.random(0, n_steps=24, p_exhaust=0.3, p_deny=0.2))
    )
    rows = []
    for label, frac, faults in cases:
        pool = max(2, int(round(full * frac)))
        t0 = time.perf_counter()
        sched = run(pool, faults)
        wall = time.perf_counter() - t0
        st = sched.stats
        terminal = (len(sched.finished) + len(sched.preempted)
                    + len(sched.rejected))
        rows.append({
            "label": label,
            "pool_frac": frac,
            "pool_pages": pool,
            "batch": batch,
            "chaos": faults is not None,
            "tokens": st.tokens,
            "wall_s": wall,
            "tokens_per_s": st.tokens / wall,
            "completed": len(sched.finished),
            "evictions": st.n_evictions,
            "preemptions": st.n_preempted,
            "rejections": st.n_rejected,
            "reject_reasons": dict(st.reject_reasons),
            "deadline_misses": st.deadline_misses,
            "all_terminal": terminal == n_reqs,
            "outputs_match": all(
                r.generated == reference[rid]
                for rid, r in sched.finished.items()
            ),
        })
    return rows


def family_rows(
    archs: Sequence[str] = ("rwkv6", "mamba"),
    batch_sizes: Sequence[int] = (2, 4),
    n_new: int = 8,
    max_prompt: int = 16,
    quick: bool = False,
    repeats: int = 3,
) -> List[Dict]:
    """Recurrent families through the shared scheduler, one row per
    (arch, batch).

    Every row first runs the workload once untimed to (a) compile all jit
    entries and (b) assert the scheduled tokens equal the direct forward
    fed the same chunks (``outputs_match`` — CI fails the artifact when
    False).  The strided PACK efficiency is ≈ 1 by
    construction (dense fixed-stride state rows, no index tax) while BASE
    efficiency is the occupancy — the serving-side contrast between the
    paper's two packed burst dialects.
    """
    if quick:
        batch_sizes = (2,)
    arch_cfg = {"rwkv6": "rwkv6-3b", "mamba": "yi-6b"}
    rng = np.random.default_rng(5)
    rows = []
    for arch in archs:
        cfg = smoke_config(arch_cfg[arch])
        model = RecurrentLM(cfg, jax.random.PRNGKey(0), arch=arch)
        for b in batch_sizes:
            lens = rng.integers(4, max_prompt + 1, b)
            prompts = [rng.integers(0, cfg.vocab, int(n)).astype(np.int32)
                       for n in lens]
            want = recurrent_reference_generate(
                model, model.init_pool(b), prompts, n_new, chunk=CHUNK
            )

            def _run() -> Scheduler:
                sched = Scheduler(model, model.init_pool(b), chunk=CHUNK)
                for i, p in enumerate(prompts):
                    sched.submit(Request(rid=i, prompt=p, max_new=n_new))
                sched.run()
                return sched

            warm = _run()  # warmup + correctness gate
            out = {rid: r.generated for rid, r in warm.finished.items()}
            match = out == {i: want[i] for i in range(b)}
            wall = float("inf")
            for _ in range(max(1, repeats)):
                t0 = time.perf_counter()
                sched = _run()
                wall = min(wall, time.perf_counter() - t0)
            st = sched.stats
            fam = sched.family
            rows.append({
                "family": arch,
                "batch": b,
                "tokens": st.tokens,
                "wall_s": wall,
                "tokens_per_s": st.tokens / wall,
                "decode_steps": st.decode_steps,
                "pack_kib": st.pack_bytes / 2**10,
                "base_kib": st.base_bytes / 2**10,
                "pack_eff": st.pack_efficiency,
                "base_eff": st.base_efficiency,
                "prefill_pack_eff": st.prefill_pack_efficiency,
                "prefill_base_eff": st.prefill_base_efficiency,
                "prompt_tokens": sum(len(p) for p in prompts),
                "state_slot_bytes": fam.state_bytes(1),
                "pool_bytes": fam.pool_bytes,
                "outputs_match": match,
            })
    return rows


def spec_rows(
    batch_sizes: Sequence[int] = (1, 2, 4, 8),
    n_new: int = 48,
    max_prompt: int = 16,
    quick: bool = False,
    repeats: int = 5,
    spec_ks: Sequence[int] = (2, 4),
    kv_dtype: str = None,
) -> List[Dict]:
    """Speculative-decoding sweep: one row per (batch, spec_k).

    Same weights and scheduler as :func:`serving_rows`; the ``spec_k=1``
    baseline timed per batch is the plain fused-decode path, re-run on
    this sweep's decode-heavy workload (short prompts, long generations —
    speculation amortizes *decode-time* page walks, so the decode phase is
    what the ratio must isolate; prompt processing is the prefill rows'
    story).  Each speculative row re-runs the identical workload through a
    ``spec_k``-wide model — the n-gram drafter proposes ``spec_k - 1``
    tokens per step and one ``paged_verify`` launch scores all of them in
    a single clamped page walk — and **asserts the emitted outputs are
    bit-for-bit the plain greedy outputs** (``outputs_match``; CI fails
    the artifact when False).  Reported next to tokens/s:
    ``acceptance_rate`` (drafts the verifier kept), ``speedup_vs_plain``
    (decode tokens/s over the spec_k=1 run), and the verify-dialect
    PACK/BASE efficiencies (BASE is the K-narrow-walks counterfactual the
    multi-query walk replaces).
    """
    if quick:
        batch_sizes = (1, 4)
        n_new = 24
    cfg = smoke_config("yi-6b")
    models = {
        k: PagedLM(cfg, jax.random.PRNGKey(0), impl="ref", spec_k=k,
                   kv_dtype=kv_dtype)
        for k in (1,) + tuple(spec_ks)
    }

    def _spec_cache(model: PagedLM, batch: int) -> PagedKVCache:
        # Longer slots than the main sweep: generations here run past
        # MAX_LEN so the decode phase dominates the measured wall.
        return PagedKVCache.create(
            model.cfg, batch=batch, max_len=2 * MAX_LEN, page=PAGE,
            kv_dtype=model.kv_dtype,
        )

    rng = np.random.default_rng(0)
    rows = []
    for b in batch_sizes:
        lens = rng.integers(4, max_prompt + 1, b)
        prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
                   for n in lens]

        def _time(model):
            def once():
                cache = _spec_cache(model, len(prompts))
                sched = Scheduler(model, cache, chunk=CHUNK)
                for i, p in enumerate(prompts):
                    sched.submit(Request(rid=i, prompt=p, max_new=n_new))
                sched.run()
                return sched

            once()  # warmup: same workload, all jit entries
            wall, sched = float("inf"), None
            for _ in range(max(1, repeats)):
                t0 = time.perf_counter()
                s = once()
                dt = time.perf_counter() - t0
                if dt < wall:
                    wall, sched = dt, s
            return sched, wall

        plain_sched, plain_wall = _time(models[1])
        plain_out = {rid: r.generated
                     for rid, r in plain_sched.finished.items()}
        plain_tps = plain_sched.stats.tokens / plain_wall
        for k in spec_ks:
            sched, wall = _time(models[k])
            out = {rid: r.generated for rid, r in sched.finished.items()}
            st = sched.stats
            rows.append({
                "batch": b,
                "spec_k": k,
                "tokens": st.tokens,
                "wall_s": wall,
                "tokens_per_s": st.tokens / wall,
                "plain_tokens_per_s": plain_tps,
                "speedup_vs_plain": (st.tokens / wall) / plain_tps,
                "acceptance_rate": st.acceptance_rate,
                "drafted": st.n_drafted,
                "accepted": st.n_accepted,
                "emitted": st.n_emitted,
                "verify_steps": st.spec_steps,
                "plain_decode_steps": plain_sched.stats.decode_steps,
                "pack_eff": st.pack_efficiency,
                "base_eff": st.base_efficiency,
                "kv_elem_bits": models[k].kv_elem_bits,
                "outputs_match": out == plain_out,
            })
    return rows


def serving_rows(
    batch_sizes: Sequence[int] = (1, 2, 4, 8),
    n_new: int = 16,
    max_prompt: int = 24,
    quick: bool = False,
    repeats: int = 5,
    kv_dtype: str = None,
) -> List[Dict]:
    """One row per batch size; ``kv_dtype='int8'`` serves from quantized
    pools (quantize-on-write + in-kernel dequant) — same prompts, same
    workload, so rows are directly comparable to the full-precision sweep.
    """
    if quick:
        batch_sizes = (1, 4)
        n_new = 8
    cfg = smoke_config("yi-6b")
    model = PagedLM(cfg, jax.random.PRNGKey(0), impl="ref", kv_dtype=kv_dtype)
    rng = np.random.default_rng(0)
    rows = []
    for b in batch_sizes:
        lens = rng.integers(4, max_prompt + 1, b)
        prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
                   for n in lens]
        _run_once(model, prompts, n_new)  # warmup: same workload, all jits
        wall = float("inf")
        for _ in range(max(1, repeats)):
            t0 = time.perf_counter()
            sched = _run_once(model, prompts, n_new)
            wall = min(wall, time.perf_counter() - t0)
        st = sched.stats
        rows.append({
            "batch": b,
            "tokens": st.tokens,
            "wall_s": wall,
            "tokens_per_s": st.tokens / wall,
            "decode_steps": st.decode_steps,
            "steps_per_s": st.decode_steps / wall,
            "evictions": st.n_evictions,
            "pack_kib": st.pack_bytes / 2**10,
            "base_kib": st.base_bytes / 2**10,
            "pack_eff": st.pack_efficiency,
            "base_eff": st.base_efficiency,
            "prompt_tokens": sum(len(p) for p in prompts),
            "prefill_steps": st.prefill_steps,
            "prefill_tokens_per_s": _prefill_throughput(
                model, prompts, repeats
            ),
            "prefill_pack_eff": st.prefill_pack_efficiency,
            "prefill_base_eff": st.prefill_base_efficiency,
            "kv_elem_bits": model.kv_elem_bits,
            "pool_bytes": sched.cache.pool_bytes,
        })
    return rows
