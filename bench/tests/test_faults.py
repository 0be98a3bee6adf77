"""A run whose timed path is broken underneath comes out not correct.

Each test skips the harness's look for a chip (``--rehearse``) and drives
the rest of a run in this process, with the family that the timed path
serves from broken after it is built, once for each fault a serving cell
can have: a token altered where it is produced, and a decode step that
leaves the serving state (the K/V pages) as it was.  The
faults that need a training step or several chips do not apply.
"""
import contextlib
import dataclasses
import importlib
import io
import json

import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as harness


def alter_tokens(fam):
    orig = fam.decode_steps

    def decode_steps(tokens, active, n):
        return (np.asarray(orig(tokens, active, n)) + 1) % fam.vocab

    fam.decode_steps = decode_steps


def keep_state(fam):
    orig = fam.decode_steps

    def decode_steps(tokens, active, n):
        # Copies: the call donates the K/V pools it is given.
        k, v = jnp.copy(fam.cache.k_pages), jnp.copy(fam.cache.v_pages)
        out = orig(tokens, active, n)
        fam.cache = dataclasses.replace(fam.cache, k_pages=k, v_pages=v)
        return out

    fam.decode_steps = decode_steps


CASES = [
    ("yi6b-attn-l4.longctx", "paged_attn", alter_tokens),
    ("yi6b-attn-l4.longctx", "paged_attn", keep_state),
]


@pytest.mark.parametrize("cell,family,fault", CASES,
                         ids=[f"{c}-{f.__name__}" for c, _, f in CASES])
def test_fault_is_not_correct(cell, family, fault, monkeypatch):
    fam_mod = importlib.import_module(f"bench.families.{family}")
    build = fam_mod.build

    def broken(cfg, seed):
        fam = build(cfg, seed)
        fault(fam)
        return fam

    monkeypatch.setattr(fam_mod, "build", broken)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = harness.main(["--workload", cell, "--seed", "3000000021",
                           "--seconds", "3", "--rehearse"])
    assert rc == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert result["correct"] is False, result["checks"]
