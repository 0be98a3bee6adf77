"""RWKV6 served through the ``Scheduler`` against the plain reference.

``RecurrentLM`` serves ragged requests from state slots at a small size on
seeded random weights (``bench/reference/rwkv6.py`` draws them, and
``bench/families/rwkv6.py`` renames them into the program's tree).  Every
logit the served path computes — after each prompt's last prefill chunk and
at every decode step — is compared with the reference's full forward over
the same tokens.  The comparison is tight enough that the parent's decay
form, a dropped WKV state carry, or computing at float8 fails it.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import rwkv6 as rwkv_mod
from repro.serve import RecurrentLM, Request, Scheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.families import rwkv6 as adapter  # noqa: E402
from bench.reference import rwkv6 as ref  # noqa: E402

CFG = {
    "name": "rwkv6-test", "hidden_size": 128, "attention_hidden_size": 128,
    "head_size": 64, "head_size_divisor": 8, "intermediate_size": 224,
    "num_hidden_layers": 2, "vocab_size": 256, "layer_norm_epsilon": 1e-5,
    "time_mix_extra_dim": 32, "time_decay_extra_dim": 64,
    "param_dtype": "float32", "dtype": "float32",
}
BF16 = dict(CFG, param_dtype="bfloat16", dtype="bfloat16")
SEED = 3000000021
PROMPTS = (9, 5, 12)
MAX_NEW = 6

#: Float32 on both sides, and XLA:CPU multiplies float32 exactly as the
#: reference's ``HIGHEST`` does: only the order of the sums differs (the
#: reference's whole-sequence products against one token a step), so the
#: logits, of unit scale, agree to a few float32 ulps of their sums.
TOL_F32 = 1e-4
#: bfloat16 weights and activations against the float32 reference on the
#: same (bfloat16-valued) weights: each product rounds its inputs to 8
#: mantissa bits, about 0.4% apiece, through two layers and the head.
#: Computing at float8 e4m3 (3 mantissa bits) lands far above it.
TOL_BF16 = 0.15


def _served(cfg, seed=SEED):
    """Serve :data:`PROMPTS` through the scheduler; returns the requests
    and every logit row the served path computed, as
    ``{rid: {position: logits}}``."""
    model = _model(cfg, seed)
    sched = Scheduler(model, model.init_pool(len(PROMPTS)), chunk=4)
    fam = sched.family
    seen = {}

    def owner(slot):
        return next(r for r in sched.resident if r.slot == slot)

    prefill = fam.prefill_batch

    def prefill_batch(tokens, counts, slots, starts):
        out = prefill(tokens, counts, slots, starts)
        for i, c in enumerate(counts):
            r = owner(int(slots[i])) if c else None
            if r is not None and starts[i] + c == r.prompt_len:
                seen.setdefault(r.rid, {})[r.prompt_len - 1] = out[i]
        return out

    chain = model.decode_chain

    def decode_chain(tensors, tokens, active, n):
        # One step per launch, bit for bit the chain.  The decode program
        # hands out only tokens, so each step's logits are read by feeding
        # the same tokens to a copy of the state through the prefill
        # program (one-token rows, one per slot), and the step's served
        # tokens must be their argmax.
        outs = []
        slots = np.arange(len(active))
        for s in range(n):
            copy = {k: jnp.copy(t) for k, t in tensors.items()}
            _, logits = model.prefill_chunk(
                copy, tokens[:, None], active.astype(np.int32), slots,
                np.zeros(len(active), bool))
            logits = np.asarray(logits)
            tensors, toks = chain(tensors, tokens, active, 1)
            for slot in np.nonzero(active)[0]:
                r = owner(int(slot))
                seen.setdefault(r.rid, {})[r.prompt_len + r.fed + s] = \
                    logits[slot]
                assert toks[0, slot] == np.argmax(logits[slot])
            tokens = toks[0]
            outs.append(toks)
        return tensors, np.concatenate(outs)

    fam.prefill_batch = prefill_batch
    model.decode_chain = decode_chain
    rng = np.random.default_rng(seed % 1000)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg["vocab_size"], n)
                    .astype(np.int32), max_new=MAX_NEW)
            for i, n in enumerate(PROMPTS)]
    for i, r in enumerate(reqs):
        sched.submit(r)
        if i == 0:
            sched.step()            # ragged: the others arrive later
    sched.run()
    return reqs, seen


def _reference(cfg, reqs, seen, control=False, seed=SEED):
    """The reference's logits at the served positions, stacked like
    :func:`_compare` stacks the served ones."""
    seqs = [np.concatenate([r.prompt, r.generated[:-1]]) for r in reqs]
    rows = [np.asarray(sorted(seen[r.rid])) for r in reqs]
    out = ref.logits(ref.init_weights(cfg, seed), cfg, seqs, rows, 256, 16,
                     control=control)
    return np.concatenate(out)


def _stack(reqs, seen):
    return np.stack([seen[r.rid][p] for r in reqs for p in sorted(seen[r.rid])])


def _gap(cfg):
    reqs, seen = _served(cfg)
    for r in reqs:
        # Prefill's logits and one per decode step but the last, which no
        # request reads.
        assert sorted(seen[r.rid]) == list(
            range(len(r.prompt) - 1, len(r.prompt) + MAX_NEW - 1))
    got = _stack(reqs, seen)
    return float(np.abs(got - _reference(cfg, reqs, seen)).max()), reqs, seen


def test_served_logits_match_the_reference():
    gap, reqs, seen = _gap(CFG)
    assert gap < TOL_F32
    # The served tokens are the argmax of the logits compared.
    for r in reqs:
        assert r.generated == [int(np.argmax(seen[r.rid][p]))
                               for p in sorted(seen[r.rid])]


def _parent_decay(p, x_w):
    """The parent's decay: tanh on the LoRA's first factor, linear in x."""
    w_log = p["w_base"].astype(jnp.float32) + jnp.einsum(
        "bsd,dr,rhk->bshk", x_w.astype(jnp.float32),
        jnp.tanh(p["wa"].astype(jnp.float32)), p["wb"].astype(jnp.float32))
    return jnp.exp(-jnp.exp(w_log))


def _no_carry(wkv):
    """``wkv`` (decode's one token, or prefill's chunk) fed a zero state in
    place of the carried one."""
    def dropped(r, k, v, w, u, s, *rest):
        return wkv(r, k, v, w, u, jnp.zeros_like(s), *rest)
    return dropped


@pytest.mark.parametrize("faults", [
    lambda: {"decay": _parent_decay},
    lambda: {name: _no_carry(getattr(rwkv_mod, name))
             for name in ("wkv6_step", "wkv6_masked")},
], ids=["parent-decay-form", "dropped-state-carry"])
def test_broken_model_fails_the_tolerance(monkeypatch, faults):
    for name, fault in faults().items():
        monkeypatch.setattr(rwkv_mod, name, fault)
    gap, _, _ = _gap(CFG)
    assert gap > 100 * TOL_F32


def test_bf16_path_within_its_tolerance_and_fp8_outside():
    gap, reqs, seen = _gap(BF16)
    assert gap < TOL_BF16
    want = _reference(BF16, reqs, seen)
    fp8 = _reference(BF16, reqs, seen, control=True)
    assert float(np.abs(fp8 - want).max()) > TOL_BF16


def _model(cfg=CFG, seed=SEED):
    return RecurrentLM(adapter.arch(cfg), None,
                       params=adapter.params(cfg, ref.init_weights(cfg, seed)))


def _dirty_pool(model, batch, seed=1):
    """A pool whose every row holds nonzero state, as slots left by earlier
    occupants do."""
    rng = np.random.default_rng(seed)
    return {k: jnp.asarray(rng.normal(size=t.shape) * 0.5, t.dtype)
            for k, t in model.init_pool(batch).tensors.items()}


def _numpy(tensors):
    return {k: np.asarray(t) for k, t in tensors.items()}


def test_chunk_prefill_matches_token_by_token():
    """One call of the chunk prefill: a full row, a fresh row of count < C
    whose padding positions hold real token ids, a row carrying its state
    on, and a padding row aliasing the fresh row's slot.  Each row's logits
    and final state are those of its own tokens fed one at a time through
    the per-token body; the slot outside the call is untouched."""
    model = _model()
    c, batch = 8, 4
    rng = np.random.default_rng(7)
    toks = rng.integers(0, CFG["vocab_size"], (4, c)).astype(np.int32)
    counts = np.array([8, 3, 5, 0], np.int32)
    slots = np.array([2, 0, 3, 0], np.int32)
    fresh = np.array([False, True, False, False])
    before = _numpy(_dirty_pool(model, batch))
    after, logits = model.prefill_chunk(
        {k: jnp.asarray(v) for k, v in before.items()}, toks, counts, slots,
        fresh)
    after, logits = _numpy(after), np.asarray(logits)

    step = jax.jit(model._token)
    head = jax.jit(model._head)
    want = {k: v.copy() for k, v in before.items()}
    for k in want:
        want[k][:, 0] = 0                        # the fresh row's slot
    state = {k: jnp.asarray(v) for k, v in want.items()}
    want_logits = {}
    for t in range(c):
        tok = np.zeros(batch, np.int32)
        act = np.zeros(batch, bool)
        for i in range(3):
            if t < counts[i]:
                tok[slots[i]], act[slots[i]] = toks[i, t], True
        x, state = step(model.params, state, tok, act)
        lg = np.asarray(head(model.params, x))
        for i in range(3):
            if t == counts[i] - 1:
                want_logits[i] = lg[slots[i]]
    state = _numpy(state)
    for i in range(3):
        np.testing.assert_allclose(logits[i], want_logits[i],
                                   rtol=1e-5, atol=1e-5)
    for k in before:
        # Padding positions (row 1's last five ids, the padding row's all)
        # moved nothing: slot 0 holds row 1's three tokens only.
        for slot in (0, 2, 3):
            np.testing.assert_allclose(after[k][:, slot], state[k][:, slot],
                                       rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(after[k][:, 1], before[k][:, 1])


def test_all_padding_prefill_writes_nothing():
    """The warm-up's calls: every row padding, aliasing slot 0, some
    flagged fresh.  The pool comes back bit for bit."""
    model = _model()
    for rows in (1, 4):
        before = _numpy(_dirty_pool(model, 4))
        z = np.zeros(rows, np.int32)
        toks = np.arange(rows * 8, dtype=np.int32).reshape(rows, 8)
        after, _ = model.prefill_chunk(
            {k: jnp.asarray(v) for k, v in before.items()}, toks, z, z,
            np.ones(rows, bool))
        for k, v in _numpy(after).items():
            np.testing.assert_array_equal(v, before[k])


def test_chunked_wkv6_matches_the_token_serial_recurrence():
    rng = np.random.default_rng(5)
    b, t, h, hd = 2, 64, 2, rwkv_mod.HEAD_DIM
    r, k, v = (jnp.asarray(rng.normal(size=(b, t, h, hd)), jnp.float32)
               for _ in range(3))
    w = jnp.asarray(rng.uniform(0.5, 1.0, (b, t, h, hd)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(h, hd)), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(b, h, hd, hd)), jnp.float32)
    y, s = rwkv_mod.wkv6(r, k, v, w, u, s0, chunk=16)
    want, st = [], s0
    for i in range(t):
        yi, st = rwkv_mod.wkv6_step(r[:, i], k[:, i], v[:, i], w[:, i], u, st)
        want.append(yi)
    np.testing.assert_allclose(np.asarray(y), np.stack(want, axis=1),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(st),
                               rtol=1e-4, atol=1e-4)
