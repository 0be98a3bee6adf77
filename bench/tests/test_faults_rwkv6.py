"""A run of ``rwkv6-3b-l4.chat-batch`` whose timed path is broken
underneath comes out not correct.

As ``test_faults.py`` does for the paged cell: the harness runs in this
process at the rehearsal sizes (``--rehearse``) with the family broken
after it is built, once with a token altered where decode produces it, and
once with decode steps that leave the serving state (the state slots) as
it was, so that every decode step starts from the state its prefill left.
"""
import contextlib
import io
import json

import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as harness
from bench.families import rwkv6 as fam_mod

CELL = "rwkv6-3b-l4.chat-batch"


def alter_tokens(fam):
    orig = fam.decode_steps

    def decode_steps(tokens, active, n):
        return (np.asarray(orig(tokens, active, n)) + 1) % fam.vocab

    fam.decode_steps = decode_steps


def keep_state(fam):
    orig = fam.decode_steps

    def decode_steps(tokens, active, n):
        # Copies: the call donates the state pools it is given.
        kept = {k: jnp.copy(t) for k, t in fam.pool.tensors.items()}
        out = orig(tokens, active, n)
        fam.pool.tensors = kept
        return out

    fam.decode_steps = decode_steps


@pytest.mark.parametrize("fault", [alter_tokens, keep_state],
                         ids=lambda f: f.__name__)
def test_fault_is_not_correct(fault, monkeypatch):
    build = fam_mod.build

    def broken(cfg, seed):
        fam = build(cfg, seed)
        fault(fam)
        return fam

    monkeypatch.setattr(fam_mod, "build", broken)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = harness.main(["--workload", CELL, "--seed", "3000000021",
                           "--seconds", "3", "--rehearse"])
    assert rc == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert result["correct"] is False, result["checks"]
