"""Chaos suite: the scheduler survives pressure and faults.

The robustness contract under test (ISSUE 7 acceptance criteria):

* a forced pool-exhaustion chaos run completes with **zero crashes**;
* every submitted request ends in **exactly one terminal state**
  (finished / preempted / rejected);
* outputs of non-preempted requests are **bit-for-bit equal** to the
  fault-free run (eviction replay, prefix re-prefill, and deferred
  allocation are all invisible to the tokens);
* the step-wise invariant checker (`repro.serve.faults`) **never fires** —
  pool free/owned partition, refcount conservation, slot bookkeeping, and
  host-shadow consistency hold after every single step;
* all of the above in fp32 and int8, with and without prefix sharing.

CI runs this file over a seed matrix via ``REPRO_CHAOS_SEED_BASE``.
"""
import os

import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.runtime import FaultToleranceConfig, StragglerWatchdog
from repro.serve import (
    FaultPlan,
    InvariantViolation,
    PagedKVCache,
    PagedLM,
    RejectReason,
    Request,
    RequestState,
    Scheduler,
    check_scheduler_invariants,
    terminal_states,
)

CFG = smoke_config("yi-6b")
PAGE = 4
MAX_LEN = 32
MODELS = {
    "fp32": PagedLM(CFG, jax.random.PRNGKey(0), impl="ref"),
    "int8": PagedLM(CFG, jax.random.PRNGKey(0), impl="ref", kv_dtype="int8"),
}
KV_DTYPE = {"fp32": None, "int8": "int8"}

# CI shifts the chaos seed window per matrix job; locally it is seeds 0..N.
SEED_BASE = int(os.environ.get("REPRO_CHAOS_SEED_BASE", "0"))
SEEDS_PER_CASE = 3


def chaos_drive(sched, requests, max_steps: int = 500):
    """Drive to drain with the invariant oracle asserted after EVERY step.

    Submissions are non-strict: rejection is a terminal outcome here, not
    an error.  Returns finished outputs only.
    """
    for r in requests:
        sched.submit(r, strict=False)
    check_scheduler_invariants(sched, requests)
    steps = 0
    while sched.queue or sched.resident:
        sched.step()
        check_scheduler_invariants(sched, requests)
        steps += 1
        assert steps < max_steps, "chaos run failed to drain (deadlock)"
    return {rid: r.generated for rid, r in sorted(sched.finished.items())}


def _mk_requests(rng, n_reqs: int, max_new: int, sys_pages: int = 1,
                 priorities=(0, 1), budget_every: int = 3):
    """Mixed traffic: shared system prompt + random tails, alternating
    priorities, and a tight replay budget on every ``budget_every``-th
    request so preemption is reachable under heavy eviction."""
    sys_prompt = rng.integers(0, CFG.vocab, sys_pages * PAGE, dtype=np.int64)
    reqs = []
    for i in range(n_reqs):
        if sys_pages and rng.random() < 0.7:
            tail = rng.integers(0, CFG.vocab, int(rng.integers(1, 6)),
                                dtype=np.int64)
            p = np.concatenate([sys_prompt, tail])
        else:
            p = rng.integers(0, CFG.vocab, int(rng.integers(1, 11)),
                             dtype=np.int64)
        budget = None
        if budget_every and i % budget_every == budget_every - 1:
            budget = len(p) + max_new  # one cheap replay, not two
        reqs.append(Request(
            rid=i, prompt=np.asarray(p, np.int32), max_new=max_new,
            priority=priorities[i % len(priorities)], replay_budget=budget,
        ))
    return reqs


# ---------------------------------------------------------------------------
# The headline acceptance run: forced pool exhaustion across the full matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv", ["fp32", "int8"])
@pytest.mark.parametrize("sharing", [False, True])
def test_chaos_pool_pressure_matrix(kv, sharing):
    model = MODELS[kv]
    for seed in range(SEED_BASE, SEED_BASE + SEEDS_PER_CASE):
        rng = np.random.default_rng(seed)
        max_new = 4
        requests = _mk_requests(rng, n_reqs=4, max_new=max_new)
        worst = max(
            -(-(len(r.prompt) + max_new - 1) // PAGE) for r in requests
        )
        pool = worst + 2  # tight: organic contention on top of the faults

        def run(faults):
            cache = PagedKVCache.create(
                CFG, batch=2, max_len=MAX_LEN, page=PAGE,
                pool_pages=pool, kv_dtype=KV_DTYPE[kv],
            )
            reqs = [
                Request(rid=r.rid, prompt=r.prompt.copy(),
                        max_new=r.max_new, priority=r.priority,
                        replay_budget=r.replay_budget)
                for r in requests
            ]
            sched = Scheduler(model, cache, chunk=3, prefix_sharing=sharing,
                              faults=faults)
            out = chaos_drive(sched, reqs)
            return out, sched, reqs

        clean_out, clean_sched, _ = run(None)
        plan = FaultPlan.random(seed, n_steps=20, p_exhaust=0.35,
                                p_deny=0.2, p_drop=0.2)
        chaos_out, chaos_sched, chaos_reqs = run(plan)

        # Every request reached exactly one terminal state, zero crashes.
        states = terminal_states(chaos_reqs)
        assert set(states.values()) <= {"finished", "preempted"}
        # Non-preempted outputs are bit-for-bit the fault-free outputs.
        for rid, toks in chaos_out.items():
            assert toks == clean_out[rid], (
                f"seed {seed}: rid {rid} diverged under chaos"
            )
        # The fault-free leg finished everything (budgets are generous
        # without injected exhaustion).
        assert set(clean_out) == {r.rid for r in requests}
        # Drained pool is leak-free even after forced churn.
        chaos_sched.flush_prefix_cache()
        assert sorted(chaos_sched.cache.free) == list(range(pool))



# ---------------------------------------------------------------------------
# Degradation: a mixed-SLA workload under shrinking pools
# ---------------------------------------------------------------------------


def _degradation_run(pool_frac, faults):
    """Six requests (alternating priorities, deadlines on the odd half,
    replay budgets on every third) for three slots, in a pool cut to
    ``pool_frac`` of the pages all six need fully grown at once."""
    page, chunk, max_len, max_new = 8, 8, 64, 8
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, CFG.vocab, int(n)).astype(np.int32)
               for n in rng.integers(4, 25, 6)]
    full = sum(-(-(len(p) + max_new - 1) // page) for p in prompts)
    cache = PagedKVCache.create(CFG, batch=3, max_len=max_len, page=page,
                                pool_pages=max(2, round(full * pool_frac)))
    reqs = [Request(rid=i, prompt=p, max_new=max_new, priority=i % 2,
                    deadline_steps=40 if i % 2 else None,
                    replay_budget=(2 * (len(p) + max_new) if i % 3 == 0
                                   else None))
            for i, p in enumerate(prompts)]
    sched = Scheduler(MODELS["fp32"], cache, chunk=chunk, faults=faults)
    return chaos_drive(sched, reqs), reqs


@pytest.mark.parametrize("pool_frac,chaos", [
    (1.0, False), (0.5, False), (0.12, False), (0.5, True),
], ids=["pool1", "pool0.5", "pool0.12", "chaos-pool0.5"])
def test_degradation_all_terminal_outputs_match_full_pool(pool_frac, chaos):
    """Under pool pressure, and under seeded faults on a halved pool, every
    request reaches a terminal state and every finished output equals the
    fault-free full-pool run's."""
    reference, _ = _degradation_run(1.0, None)
    assert len(reference) == 6
    faults = (FaultPlan.random(0, n_steps=24, p_exhaust=0.3, p_deny=0.2)
              if chaos else None)
    out, reqs = _degradation_run(pool_frac, faults)
    states = terminal_states(reqs)
    assert len(states) == len(reqs)
    for rid, toks in out.items():
        assert toks == reference[rid]


# ---------------------------------------------------------------------------
# Targeted fault classes
# ---------------------------------------------------------------------------


def test_denied_allocation_defers_and_stays_consistent():
    """deny_alloc is the mid-flight OutOfPages scenario: growth must defer —
    never raise, never leave a partially-grown table behind."""
    model = MODELS["fp32"]
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, CFG.vocab, 4).astype(np.int32)
    plan = FaultPlan(seed=2, deny_alloc_at=frozenset(range(1, 10)))

    def run(faults):
        cache = PagedKVCache.create(CFG, batch=1, max_len=MAX_LEN, page=PAGE)
        sched = Scheduler(model, cache, chunk=4, faults=faults)
        reqs = [Request(rid=0, prompt=prompt.copy(), max_new=8)]
        return chaos_drive(sched, reqs), sched

    clean, _ = run(None)
    chaos, sched = run(plan)
    assert chaos == clean
    # Denied steps really happened (the run outlasted the fault window).
    assert sched._step > 9


def test_forced_exhaustion_single_resident_self_evicts():
    """Pool exhaustion with one resident used to be a raise; now the request
    defers by self-eviction and replays bit-for-bit once the fault clears.

    A second queued request keeps lookahead prealloc off (lookahead only
    runs with an empty queue), so the resident grows on demand — step 3 is
    its first page-boundary growth, where the injected exhaustion lands."""
    model = MODELS["fp32"]
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, CFG.vocab, 4).astype(np.int32)
               for _ in range(2)]
    plan = FaultPlan(seed=3, exhaust_at=frozenset({2, 3}))

    def run(faults):
        cache = PagedKVCache.create(CFG, batch=1, max_len=MAX_LEN, page=PAGE)
        sched = Scheduler(model, cache, chunk=4, faults=faults)
        reqs = [Request(rid=i, prompt=p.copy(), max_new=8)
                for i, p in enumerate(prompts)]
        return chaos_drive(sched, reqs), sched, reqs

    clean, _, _ = run(None)
    chaos, sched, reqs = run(plan)
    assert chaos == clean
    assert reqs[0].n_evictions >= 1  # it was actually pushed out mid-flight
    assert sched.stats.n_preempted == 0


def test_replay_budget_exhaustion_preempts_with_partial_output():
    model = MODELS["fp32"]
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, CFG.vocab, 4).astype(np.int32)
               for _ in range(2)]
    plan = FaultPlan(seed=4, exhaust_at=frozenset({2, 3}))
    cache = PagedKVCache.create(CFG, batch=1, max_len=MAX_LEN, page=PAGE)
    sched = Scheduler(model, cache, chunk=4, faults=plan)
    req = Request(rid=0, prompt=prompts[0], max_new=8, replay_budget=0)
    other = Request(rid=1, prompt=prompts[1], max_new=8)
    out = chaos_drive(sched, [req, other])
    assert set(out) == {1}  # rid 0 never finished …
    assert req.state is RequestState.PREEMPTED
    assert sched.preempted[0] is req
    assert len(req.generated) >= 1  # … but its partial output survives
    assert sched.stats.n_preempted == 1
    assert sched.stats.n_evictions == 0  # budget burned on first eviction
    # Preemption released everything: pool back to pristine.
    assert sorted(sched.cache.free) == list(range(sched.cache.total_pages))


def test_preemption_picks_lowest_priority_victim():
    """Under growth pressure the victim is the lowest-priority resident —
    the old policy (youngest) would have evicted the late arrival."""
    model = MODELS["fp32"]
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, CFG.vocab, 4).astype(np.int32)
               for _ in range(2)]
    max_new = 12
    # Two residents, pool sized so decode growth contends.
    cache = PagedKVCache.create(CFG, batch=2, max_len=MAX_LEN, page=PAGE,
                                pool_pages=5)
    sched = Scheduler(model, cache, chunk=4)
    low = Request(rid=0, prompt=prompts[0], max_new=max_new, priority=0)
    high = Request(rid=1, prompt=prompts[1], max_new=max_new, priority=5)
    out = chaos_drive(sched, [low, high])
    assert low.n_evictions >= 1, "low-priority resident was never preempted"
    assert high.n_evictions == 0, "high-priority request lost its slot"
    # Replay keeps the evicted request's tokens bit-for-bit.
    ref_cache = PagedKVCache.create(CFG, batch=2, max_len=MAX_LEN, page=PAGE)
    ref = Scheduler(model, ref_cache, chunk=4)
    ref_out = chaos_drive(ref, [
        Request(rid=0, prompt=prompts[0].copy(), max_new=max_new),
        Request(rid=1, prompt=prompts[1].copy(), max_new=max_new),
    ])
    assert out == ref_out


def test_priority_orders_admission():
    model = MODELS["fp32"]
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, CFG.vocab, 4).astype(np.int32)
               for _ in range(2)]
    cache = PagedKVCache.create(CFG, batch=1, max_len=MAX_LEN, page=PAGE)
    sched = Scheduler(model, cache, chunk=4)
    batchy = Request(rid=0, prompt=prompts[0], max_new=4, priority=0)
    urgent = Request(rid=1, prompt=prompts[1], max_new=4, priority=9)
    chaos_drive(sched, [batchy, urgent])  # submitted batchy first
    assert urgent.finish_step < batchy.finish_step


def test_queued_deadline_expiry_rejects_pool_busy():
    model = MODELS["fp32"]
    rng = np.random.default_rng(7)
    cache = PagedKVCache.create(CFG, batch=1, max_len=MAX_LEN, page=PAGE)
    sched = Scheduler(model, cache, chunk=4)
    # Deadline ordering would serve the short request first, so the hog
    # outranks it by priority — the starvation the expiry path exists for.
    hog = Request(rid=0, prompt=rng.integers(0, CFG.vocab, 8).astype(np.int32),
                  max_new=12, priority=5)
    # Feasible at submit (min 2 steps ≤ 2), starved by the hog.
    late = Request(rid=1, prompt=rng.integers(0, CFG.vocab, 4)
                   .astype(np.int32), max_new=4, deadline_steps=2)
    out = chaos_drive(sched, [hog, late])
    assert set(out) == {0}
    assert late.state is RequestState.REJECTED
    assert late.reject_reason is RejectReason.POOL_BUSY
    assert sched.stats.reject_reasons == {"pool-busy": 1}
    assert sched.stats.deadline_misses == 1


def test_prefix_drop_fault_forces_reprefill_same_outputs():
    model = MODELS["fp32"]
    rng = np.random.default_rng(8)
    sys_prompt = rng.integers(0, CFG.vocab, 2 * PAGE)
    prompts = [
        np.concatenate([sys_prompt, rng.integers(0, CFG.vocab, t)])
        .astype(np.int32)
        for t in (2, 3, 4)
    ]
    plan = FaultPlan(seed=8, drop_prefix_at=frozenset(range(1, 12)))

    def run(faults):
        cache = PagedKVCache.create(CFG, batch=2, max_len=MAX_LEN, page=PAGE)
        sched = Scheduler(model, cache, chunk=4, prefix_sharing=True,
                          faults=faults)
        reqs = [Request(rid=i, prompt=p.copy(), max_new=3)
                for i, p in enumerate(prompts)]
        return chaos_drive(sched, reqs), sched

    clean, _ = run(None)
    chaos, sched = run(plan)
    assert chaos == clean
    assert sched.stats.n_prefix_drops >= 1


def test_prefix_drop_skips_counted_without_prefix_index():
    """drop_prefix against an engine with no prefix index (sharing off, or a
    recurrent family that has no token-granular units at all) must no-op
    with a counted skip — never raise, never change tokens."""
    model = MODELS["fp32"]
    rng = np.random.default_rng(12)
    prompt = rng.integers(0, CFG.vocab, 6).astype(np.int32)
    plan = FaultPlan(seed=12, drop_prefix_at=frozenset(range(1, 8)))
    cache = PagedKVCache.create(CFG, batch=1, max_len=MAX_LEN, page=PAGE)
    clean_cache = PagedKVCache.create(CFG, batch=1, max_len=MAX_LEN, page=PAGE)
    clean = chaos_drive(Scheduler(model, clean_cache, chunk=4),
                        [Request(rid=0, prompt=prompt.copy(), max_new=6)])
    sched = Scheduler(model, cache, chunk=4, faults=plan)  # sharing off
    out = chaos_drive(sched, [Request(rid=0, prompt=prompt.copy(), max_new=6)])
    assert out == clean
    assert sched.stats.n_prefix_drop_skips >= 1
    assert sched.stats.n_prefix_drops == 0


def test_chaos_recurrent_family_seed_matrix():
    """The full random fault plan against a recurrent (RWKV6) scheduler:
    prefix-drop faults are family-inapplicable (counted skips), exhaustion
    and denial degrade via the same evict/defer ladder, and surviving
    outputs stay bit-for-bit the fault-free ones."""
    from repro.serve import RecurrentLM

    rcfg = smoke_config("rwkv6-3b")
    rmodel = RecurrentLM(rcfg, jax.random.PRNGKey(0))
    for seed in range(SEED_BASE, SEED_BASE + SEEDS_PER_CASE):
        rng = np.random.default_rng(1000 + seed)
        prompts = [rng.integers(0, rcfg.vocab, int(rng.integers(2, 10)))
                   .astype(np.int32) for _ in range(4)]

        def run(faults):
            sched = Scheduler(rmodel, rmodel.init_pool(2), chunk=3,
                              faults=faults)
            reqs = [Request(rid=i, prompt=p.copy(), max_new=4)
                    for i, p in enumerate(prompts)]
            return chaos_drive(sched, reqs), sched, reqs

        clean_out, _, _ = run(None)
        plan = FaultPlan.random(seed, n_steps=16, p_exhaust=0.3,
                                p_deny=0.2, p_drop=0.5)
        chaos_out, sched, reqs = run(plan)
        states = terminal_states(reqs)
        assert set(states.values()) <= {"finished", "preempted"}
        for rid, toks in chaos_out.items():
            assert toks == clean_out[rid], (
                f"seed {seed}: rid {rid} diverged under recurrent chaos"
            )
        assert set(clean_out) == {r.rid for r in reqs}
        # Inapplicable prefix drops were skipped, not raised (plan always
        # has drop steps at p_drop=0.5 over 16 steps for these seeds).
        if plan.drop_prefix_at:
            assert sched.stats.n_prefix_drop_skips >= 1
        # Drained state pool is leak-free.
        assert sched.family.free_units == 2


def test_injected_latency_trips_straggler_watchdog():
    model = MODELS["fp32"]
    rng = np.random.default_rng(9)
    # The whole run is 2 steps (prefill, prefill+fused decode): step 1 seeds
    # the EMA baseline, step 2 carries the injected pathological latency.
    plan = FaultPlan(seed=9, delay_at={2: 30.0})
    watchdog = StragglerWatchdog(FaultToleranceConfig(straggler_factor=3.0))
    cache = PagedKVCache.create(CFG, batch=1, max_len=MAX_LEN, page=PAGE)
    sched = Scheduler(model, cache, chunk=4, faults=plan, watchdog=watchdog)
    prompt = rng.integers(0, CFG.vocab, 8).astype(np.int32)
    out = chaos_drive(sched, [Request(rid=0, prompt=prompt, max_new=8)])
    assert len(out[0]) == 8
    assert watchdog.stragglers == 1
    assert sched.stats.n_stragglers == 1
    # Nobody actually slept: the injected 30 s is bookkeeping, not wall time.
    assert sum(watchdog.history) >= 30.0


# ---------------------------------------------------------------------------
# The oracle itself, and the plan
# ---------------------------------------------------------------------------


def test_invariant_checker_fires_on_corruption():
    model = MODELS["fp32"]
    rng = np.random.default_rng(10)
    cache = PagedKVCache.create(CFG, batch=2, max_len=MAX_LEN, page=PAGE)
    sched = Scheduler(model, cache, chunk=4)
    # 8-token prompt at chunk=4: still mid-prefill (resident) after step 1.
    sched.submit(Request(rid=0, prompt=rng.integers(0, CFG.vocab, 8)
                         .astype(np.int32), max_new=4))
    sched.step()
    check_scheduler_invariants(sched)  # sane mid-flight
    sched._free_slots.append(sched.resident[0].slot)  # corrupt: slot double-owned
    with pytest.raises(InvariantViolation):
        check_scheduler_invariants(sched)


def test_fault_plan_is_deterministic_and_finite():
    a = FaultPlan.random(42, n_steps=24, p_delay=0.2)
    b = FaultPlan.random(42, n_steps=24, p_delay=0.2)
    assert a == b
    assert 0 <= a.horizon <= 24
    assert FaultPlan.none().horizon == 0
    # Probabilities actually bite at these intensities.
    assert a.exhaust_at and a.deny_alloc_at
