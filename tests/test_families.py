"""Family-agnostic serving: recurrent (RWKV6/Mamba) models through the same
scheduler as the paged transformer, token for token against direct forwards.

What this file locks down (ISSUE 8 acceptance criteria):

* scheduler-served RWKV6 and Mamba tokens equal a direct forward
  (`recurrent_reference_generate`) fed the same prompt chunks, across
  arrival orders and fused-decode interleavings;
* eviction → replay round-trips reproduce the fault-free tokens exactly
  (replay-by-re-prefill from a zeroed state row);
* mixed transformer + recurrent workloads run step-interleaved with the
  family-generic invariant oracle asserted after every step;
* the strided state read/write ops match their ref oracles bitwise and
  never disturb non-target rows;
* the strided-burst accounting dialect (`recurrent_state_streams`,
  `recurrent_decode_traffic`/`recurrent_prefill_traffic`) is internally
  consistent: PACK efficiency ≈ 1, BASE efficiency = occupancy, no index
  bus term;
* the scheduler module itself never references the paged implementation —
  it speaks only the `ServableFamily` protocol.
"""
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.packing import (
    recurrent_decode_traffic,
    recurrent_prefill_traffic,
)
from repro.core.streams import (
    BurstKind,
    StridedStream,
    recurrent_state_streams,
)
from repro.serve import (
    OutOfPages,
    PagedKVCache,
    PagedLM,
    RecurrentFamily,
    RecurrentLM,
    RecurrentStatePool,
    Request,
    RequestState,
    Scheduler,
    check_scheduler_invariants,
    recurrent_reference_generate,
    static_batch_generate,
)

RWKV_CFG = smoke_config("rwkv6-3b")
DENSE_CFG = smoke_config("yi-6b")


def _prompts(rng, vocab, lens):
    return [np.asarray(rng.integers(0, vocab, n), np.int32) for n in lens]


def _drive(sched, requests, max_steps=500):
    for r in requests:
        sched.submit(r)
    check_scheduler_invariants(sched, requests)
    steps = 0
    while sched.queue or sched.resident:
        sched.step()
        check_scheduler_invariants(sched, requests)
        steps += 1
        assert steps < max_steps, "run failed to drain"
    return {rid: r.generated for rid, r in sorted(sched.finished.items())}


# ---------------------------------------------------------------------------
# Scheduler-served tokens == direct forward fed the same chunks
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch,chunk", [("rwkv6", 4), ("rwkv6", 8),
                                        ("mamba", 4), ("mamba", 8)])
def test_scheduled_matches_direct_forward(arch, chunk):
    cfg = RWKV_CFG if arch == "rwkv6" else DENSE_CFG
    rng = np.random.default_rng(chunk + (0 if arch == "rwkv6" else 100))
    model = RecurrentLM(cfg, jax.random.PRNGKey(0), arch=arch)
    prompts = _prompts(rng, cfg.vocab, (8, 7, 12))
    max_new = 8
    want = recurrent_reference_generate(model, model.init_pool(3), prompts,
                                        max_new, chunk=chunk)
    sched = Scheduler(model, model.init_pool(3), chunk=chunk)
    reqs = [Request(rid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    out = _drive(sched, reqs)
    assert out == {i: want[i] for i in range(3)}
    assert sched.family.name == arch


def test_scheduled_matches_direct_forward_ragged_arrivals():
    """Late submissions change interleaving, never tokens: row masking keeps
    inactive slots bit-exact while other rows prefill/decode."""
    cfg = RWKV_CFG
    rng = np.random.default_rng(7)
    model = RecurrentLM(cfg, jax.random.PRNGKey(0))
    prompts = _prompts(rng, cfg.vocab, (10, 3, 6))
    max_new = 6
    want = recurrent_reference_generate(model, model.init_pool(3), prompts,
                                        max_new, chunk=4)
    sched = Scheduler(model, model.init_pool(3), chunk=4)
    reqs = [Request(rid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    sched.submit(reqs[0])
    sched.step()  # rid 0 alone in flight
    sched.submit(reqs[1])
    sched.step()
    sched.submit(reqs[2])
    while sched.queue or sched.resident:
        sched.step()
        check_scheduler_invariants(sched, reqs)
    out = {rid: r.generated for rid, r in sorted(sched.finished.items())}
    assert out == {i: want[i] for i in range(3)}


# ---------------------------------------------------------------------------
# Eviction → replay round-trip
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["rwkv6", "mamba"])
def test_eviction_replay_round_trip(arch):
    """Force-evict a mid-decode resident: replay re-prefills from a zeroed
    state row and reproduces the fault-free tokens exactly."""
    cfg = RWKV_CFG if arch == "rwkv6" else DENSE_CFG
    rng = np.random.default_rng(11)
    model = RecurrentLM(cfg, jax.random.PRNGKey(0), arch=arch)
    prompts = _prompts(rng, cfg.vocab, (9, 6))
    max_new = 8
    want = recurrent_reference_generate(model, model.init_pool(2), prompts,
                                        max_new, chunk=4)
    sched = Scheduler(model, model.init_pool(2), chunk=4)
    reqs = [Request(rid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        sched.submit(r)
    # Step until rid 1 is decoding with partial output, then evict it.
    for _ in range(50):
        sched.step()
        check_scheduler_invariants(sched, reqs)
        victim = next((r for r in sched.resident
                       if r.rid == 1 and r.state is RequestState.RUNNING
                       and r.generated and not r.done), None)
        if victim is not None:
            break
    assert victim is not None, "rid 1 never reached mid-decode"
    partial = list(victim.generated)
    sched._evict(victim)
    check_scheduler_invariants(sched, reqs)
    out = _drive(sched, [])
    assert sched.stats.n_evictions >= 1
    assert out[1][:len(partial)] == partial  # replay re-derived the prefix
    assert out == {i: want[i] for i in range(2)}


def test_out_of_slots_staggers_admission():
    """More requests than state slots: admission staggers, everyone drains."""
    cfg = RWKV_CFG
    rng = np.random.default_rng(13)
    model = RecurrentLM(cfg, jax.random.PRNGKey(0))
    prompts = _prompts(rng, cfg.vocab, (8, 7, 12, 5))
    sched = Scheduler(model, model.init_pool(2), chunk=4)
    reqs = [Request(rid=i, prompt=p, max_new=5)
            for i, p in enumerate(prompts)]
    out = _drive(sched, reqs)
    assert sorted(out) == [0, 1, 2, 3]
    assert all(len(v) == 5 for v in out.values())
    assert sched.family.free_units == 2  # all slots returned


def test_prefix_sharing_rejected_for_recurrent():
    model = RecurrentLM(RWKV_CFG, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="refcounted"):
        Scheduler(model, model.init_pool(2), prefix_sharing=True)


def test_state_pool_exhaustion_raises_typed():
    model = RecurrentLM(RWKV_CFG, jax.random.PRNGKey(0))
    fam = model.bind(model.init_pool(2))
    fam.alloc_state(0, 1)
    fam.alloc_state(1, 1)
    with pytest.raises(OutOfPages):
        fam.alloc_state(0, 1)  # double-alloc of an owned slot
    fam.release(0)
    fam.alloc_state(0, 1)  # released slot is reusable
    assert fam.free_units == 0


def test_prefill_hands_the_host_only_the_rows_logits():
    """A prefill call returns one logits row per call row, of the row's
    last fed position, chosen on the device: (rows, vocab), not a per-step
    stack."""
    model = RecurrentLM(RWKV_CFG, jax.random.PRNGKey(0))
    fam = model.bind(model.init_pool(4))
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, RWKV_CFG.vocab, (2, 8)).astype(np.int32)
    counts, slots = np.array([8, 3], np.int32), np.array([2, 0], np.int32)
    seen = []
    chunk = model.prefill_chunk

    def prefill_chunk(*args):
        tensors, logits = chunk(*args)
        seen.append(logits)
        return tensors, logits

    model.prefill_chunk = prefill_chunk
    out = fam.prefill_batch(tokens, counts, slots, np.zeros(2, np.int32))
    assert isinstance(out, np.ndarray) and out.shape == (2, RWKV_CFG.vocab)
    assert [x.shape for x in seen] == [(2, RWKV_CFG.vocab)]
    # Each row's logits are those of its last token: feeding that row alone
    # gives the same numbers.
    for i in range(2):
        alone = model.bind(model.init_pool(4))
        one = alone.prefill_batch(tokens[i:i + 1], counts[i:i + 1],
                                  slots[i:i + 1], np.zeros(1, np.int32))
        np.testing.assert_allclose(one[0], out[i], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("arch", ["rwkv6", "mamba"])
def test_no_program_output_is_a_per_step_logits_stack(arch):
    cfg = RWKV_CFG if arch == "rwkv6" else DENSE_CFG
    model = RecurrentLM(cfg, jax.random.PRNGKey(0), arch=arch)
    b, c, v = 4, 8, cfg.vocab
    pool = model.init_pool(b).tensors
    rows = np.zeros(b, np.int32)
    outs = {"prefill": model._prefill.lower(
        model.params, pool, np.zeros((b, c), np.int32), rows, rows,
        np.zeros(b, bool)).out_info}
    for n in (1, 4, 8):
        outs[f"decode{n}"] = model._decode.lower(
            model.params, pool, np.zeros(b, np.int32), np.zeros(b, bool),
            n).out_info
    for name, info in outs.items():
        shapes = [x.shape for x in jax.tree.leaves(info)]
        assert not any(len(s) == 3 and s[-1] == v for s in shapes), (name,
                                                                     shapes)
        # Prefill hands out the rows' last logits; decode only its tokens.
        assert shapes.count((b, v)) == (name == "prefill"), (name, shapes)


# ---------------------------------------------------------------------------
# Mixed transformer + recurrent workload, step-interleaved
# ---------------------------------------------------------------------------


def test_mixed_families_interleaved():
    """A paged transformer and a recurrent model serve side by side, the
    family-generic invariant oracle asserted on both after every step, and
    both match their family's reference generation bit-for-bit."""
    rng = np.random.default_rng(17)
    pm = PagedLM(DENSE_CFG, jax.random.PRNGKey(0), impl="ref")
    rm = RecurrentLM(RWKV_CFG, jax.random.PRNGKey(0))
    p_prompts = _prompts(rng, DENSE_CFG.vocab, (8, 7, 12))
    r_prompts = _prompts(rng, RWKV_CFG.vocab, (6, 11, 4))
    max_new = 6

    p_want = static_batch_generate(
        pm, PagedKVCache.create(DENSE_CFG, batch=3, max_len=32, page=4),
        p_prompts, max_new, chunk=4,
    )
    r_want = recurrent_reference_generate(rm, rm.init_pool(3), r_prompts,
                                          max_new, chunk=4)

    ps = Scheduler(pm, PagedKVCache.create(DENSE_CFG, batch=3, max_len=32,
                                           page=4), chunk=4)
    rs = Scheduler(rm, rm.init_pool(3), chunk=4)
    p_reqs = [Request(rid=i, prompt=p, max_new=max_new)
              for i, p in enumerate(p_prompts)]
    r_reqs = [Request(rid=i, prompt=p, max_new=max_new)
              for i, p in enumerate(r_prompts)]
    for r in p_reqs:
        ps.submit(r)
    for r in r_reqs:
        rs.submit(r)
    for _ in range(200):
        if not (ps.queue or ps.resident or rs.queue or rs.resident):
            break
        if ps.queue or ps.resident:
            ps.step()
            check_scheduler_invariants(ps, p_reqs)
        if rs.queue or rs.resident:
            rs.step()
            check_scheduler_invariants(rs, r_reqs)
    assert not (ps.queue or ps.resident or rs.queue or rs.resident)
    p_out = {rid: r.generated for rid, r in ps.finished.items()}
    r_out = {rid: r.generated for rid, r in rs.finished.items()}
    assert p_out == {i: p_want[i] for i in range(3)}
    assert r_out == {i: r_want[i] for i in range(3)}
    # The two families report disjoint accounting dialects.
    assert any(s.kind is BurstKind.INDIRECT
               for rec in ps.stats.records for s in rec.streams)
    assert all(s.kind is not BurstKind.INDIRECT
               for rec in rs.stats.records for s in rec.streams)
    assert any(s.kind is BurstKind.STRIDED
               for rec in rs.stats.records for s in rec.streams)


# ---------------------------------------------------------------------------
# Fresh-prefill state reset
# ---------------------------------------------------------------------------


def test_replay_zeroes_only_target_slot():
    """Replay launches nothing; the slot's next prefill from position 0
    reads a zero state, as on a fresh pool, and leaves every other slot's
    state rows as they were."""
    model = RecurrentLM(RWKV_CFG, jax.random.PRNGKey(0))
    fam = model.bind(model.init_pool(3))
    # Dirty all state rows, then replay slot 1.
    fam.pool.tensors = {
        k: t + jnp.asarray(1.0, t.dtype) for k, t in fam.pool.tensors.items()
    }
    before = {k: np.asarray(t) for k, t in fam.pool.tensors.items()}
    fam.replay(1)
    for k, t in fam.pool.tensors.items():
        np.testing.assert_array_equal(np.asarray(t), before[k])
    toks = np.arange(1, 6, dtype=np.int32)[None]
    one = np.ones(1, np.int32)
    got = fam.prefill_batch(toks, 5 * one, one, 0 * one)
    clean = model.bind(model.init_pool(3))
    want = clean.prefill_batch(toks, 5 * one, one, 0 * one)
    np.testing.assert_array_equal(got, want)
    for k, t in fam.pool.tensors.items():
        a = np.asarray(t)
        np.testing.assert_array_equal(a[:, 1], np.asarray(
            clean.pool.tensors[k])[:, 1])
        mask = np.ones(a.shape[1], bool)
        mask[1] = False
        np.testing.assert_array_equal(a[:, mask], before[k][:, mask])
    # A later chunk of the same row (start > 0) carries its state on.
    fam.prefill_batch(toks, 5 * one, one, 5 * one)
    clean.prefill_batch(toks, 5 * one, one, 5 * one)
    for k, t in fam.pool.tensors.items():
        np.testing.assert_array_equal(np.asarray(t)[:, 1], np.asarray(
            clean.pool.tensors[k])[:, 1])
        assert not np.array_equal(np.asarray(t)[:, 1],
                                  np.zeros_like(np.asarray(t)[:, 1]))


# ---------------------------------------------------------------------------
# Strided-burst accounting dialect
# ---------------------------------------------------------------------------


def test_recurrent_state_streams_descriptors():
    streams = recurrent_state_streams([1, 3], batch=4, n_layers=2,
                                      row_bytes=(4096, 512))
    # 2 slots × 2 tensors × (read + write) = 8 descriptors.
    assert len(streams) == 8
    assert all(isinstance(s, StridedStream) for s in streams)
    assert {s.base for s in streams} == {1, 3}
    assert all(s.stride == 4 and s.count == 2 for s in streams)
    assert {s.elem_bits for s in streams} == {4096 * 8, 512 * 8}
    # batch == 1 degenerates to the contiguous BASE converter (stride 1).
    assert all(s.stride == 1 for s in
               recurrent_state_streams([0], 1, 2, (64,)))


def test_recurrent_traffic_accounting():
    sb = 1000
    t = recurrent_decode_traffic(n_active=3, batch=8, state_bytes=sb)
    assert t.useful_bytes == 2 * 3 * sb
    assert t.base_bytes == 2 * 8 * sb
    assert t.index_bus_bytes_pack == 0  # the stride IS the descriptor
    assert t.useful_bytes <= t.pack_bytes < t.useful_bytes + 32
    # Idle step moves nothing under PACK.
    assert recurrent_decode_traffic(0, 8, sb).pack_bytes == 0
    p = recurrent_prefill_traffic([4, 0, 2], batch=8, state_bytes=sb)
    assert p.useful_bytes == 2 * 2 * sb  # two active rows, chunk-amortized
    assert p.base_bytes == 2 * 8 * 4 * sb  # padded pool per chunk position


def test_scheduler_records_strided_pack_efficiency():
    rng = np.random.default_rng(29)
    model = RecurrentLM(RWKV_CFG, jax.random.PRNGKey(0))
    # 4 slots, 3 requests: occupancy < 1, so BASE pays for the idle row.
    sched = Scheduler(model, model.init_pool(4), chunk=4)
    reqs = [Request(rid=i, prompt=p, max_new=5)
            for i, p in enumerate(_prompts(rng, RWKV_CFG.vocab, (8, 5, 7)))]
    _drive(sched, reqs)
    st = sched.stats
    assert st.pack_bytes > 0 and st.base_bytes > 0
    assert 0.9 <= st.pack_efficiency <= 1.0  # dense strided bursts
    assert st.base_efficiency <= 0.75  # at most 3 of 4 rows ever live
    assert st.pack_efficiency > st.base_efficiency


# ---------------------------------------------------------------------------
# Protocol purity: the scheduler speaks only ServableFamily
# ---------------------------------------------------------------------------


def test_scheduler_module_is_family_agnostic():
    import repro.serve.scheduler as sched_mod

    src = inspect.getsource(sched_mod)
    assert "PagedLM" not in src
    assert "PagedKVCache" not in src
    assert "kv_pages" not in src and "page_table" not in src
    assert "ServableFamily" in src


def test_scheduler_rejects_non_family():
    with pytest.raises(TypeError):
        Scheduler(object())
