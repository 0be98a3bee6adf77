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
* the scheduler and the protocol modules never reference the paged
  implementation or the paper's traffic model — the scheduler speaks only
  the `ServableFamily` protocol.
"""
import ast
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.serve import (
    OutOfPages,
    PagedFamily,
    PagedKVCache,
    PagedLM,
    RecurrentFamily,
    RecurrentLM,
    RecurrentStatePool,
    Request,
    RequestState,
    Scheduler,
    ServableFamily,
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
    # Both families count the same way: every token once, each request
    # max_new - 1 decode steps, the longest prompt 3 prefill chunks.
    for st in (ps.stats, rs.stats):
        assert st.tokens == 3 * max_new
        assert st.decode_steps >= max_new - 1
        assert st.prefill_steps >= 3


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
# Protocol purity: the scheduler speaks only ServableFamily
# ---------------------------------------------------------------------------


#: The per-step traffic methods the protocol no longer has.
ACCOUNTING = ("step_streams", "prefill_account", "verify_account",
              "share_account", "state_bytes")


@pytest.mark.parametrize("module", ["scheduler", "family"])
def test_scheduler_module_is_family_agnostic(module):
    mod = __import__(f"repro.serve.{module}", fromlist=[module])
    tree = ast.parse(inspect.getsource(mod))
    # Code only: the docstrings may name the families they abstract over.
    for node in ast.walk(tree):
        body = getattr(node, "body", None)
        if (isinstance(body, list) and body
                and isinstance(body[0], ast.Expr)
                and isinstance(body[0].value, ast.Constant)
                and isinstance(body[0].value.value, str)):
            node.body = body[1:] or [ast.Pass()]
    code = ast.unparse(tree)
    assert "PagedLM" not in code
    assert "PagedKVCache" not in code
    assert "kv_pages" not in code and "page_table" not in code
    assert "ServableFamily" in code
    imported = [n.module or "" for n in ast.walk(tree)
                if isinstance(n, ast.ImportFrom)]
    imported += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                 for a in n.names]
    assert not any(m.startswith("repro.core") for m in imported), imported
    for fam in (ServableFamily, PagedFamily, RecurrentFamily):
        assert not [m for m in ACCOUNTING if hasattr(fam, m)], fam


def test_scheduler_rejects_non_family():
    with pytest.raises(TypeError):
        Scheduler(object())
