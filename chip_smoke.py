#!/usr/bin/env python3
"""Bring-up smoke run: serve requests through the whole serving path on one chip.

Drives ``Scheduler`` → ``PagedLM`` → the paged Pallas kernels (decode,
prefill, verify, and the indirect write converters) at yi-6b's published
widths — d_model 4096, 32 query / 4 KV heads of 128, vocab 64000 — with
random weights drawn from ``--seed``, then ``RecurrentLM`` at rwkv6-3b's
widths through the same scheduler (its state slots).  Phases:

* ``yi-6b/bf16``  — all 32 layers, bf16 KV pages;
* ``yi-6b/int8``  — int8 KV pages with fp32 scales (depth cut, printed);
* ``yi-6b/spec4`` — speculative decoding, ``spec_k=4``: the verify kernel
  (depth cut, printed);
* ``rwkv6-3b``    — recurrent state slots (depth cut, printed: fp32 weights
  of all 32 layers would take about 12 GB).

Each phase serves ``BATCH`` requests with ragged prompts and checks, on the
device: every request reaches a terminal state; the scheduled tokens equal
the static-batch reference at the same batch shape bit for bit
(``static_batch_generate`` / ``recurrent_reference_generate``); logits are
finite; and, for the paged phases, the Pallas engine's prefill and decode
logits agree with the ``impl='ref'`` engine on the same weights within
:func:`logits_tol` (max |pallas − ref| over max |ref|), both run at float32
matmul precision so that the einsum oracle is a float32 reference.  Each
phase prints one JSON line: tokens, wall seconds including compilation,
the number of compiles and their seconds, the device's ``bytes_in_use``
while the phase's weights and pools are live, its ``peak_bytes_in_use``
(the process's running peak, so the largest phase's figure repeats in the
later rows), and whether the decode program holds Mosaic kernels
(``tpu_custom_call``).
The last line is ``{"ok": true, "device": {...}}``, printed only when every
check of every phase passed.

These are bring-up numbers, not a benchmark: times include compilation.

Usage::

    python chip_smoke.py            # on a TPU; any other backend is an error
    python chip_smoke.py --tiny     # the same phases at smoke_config sizes,
                                    # on any backend (CPU: Pallas interprets)
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# Serving geometry of the full-width run (the compile-only tests in
# tests/test_tpu_compile.py compile the kernels at these sizes).
PAGE = 16          # tokens per KV page
BATCH = 8          # sequence slots = requests per phase
MAX_LEN = 640      # tokens per slot: a 40-page table row
CHUNK = 64         # prefill chunk (128 overflows the prefill kernel's VMEM)
PROMPT_LENS = (64, 512)
MAX_NEW = 32
SPEC_K = 4
FOLLOW_UP_LAYERS = 4   # depth of the int8, spec and rwkv6 phases


def logits_tol(layers: int) -> float:
    """Bound on max |pallas - ref| / max |ref| for a model ``layers`` deep,
    both engines at float32 matmul precision.

    The attention-only model has no norms, so any difference between the
    engines grows steeply with depth: on a TPU v5e yi-6b's logits differ
    by 1.5e-5 at 4 layers, but at 32 by 1.8e-2 over bf16 pools and still
    by 6.5e-3 over float32 pools.  On the CPU at smoke widths the ``ref``
    engine against itself, with its weights perturbed by 1e-7, grows the
    same way over bf16 pools (4.7e-5 at 4 layers, 4.9e-3 at 32).

    So the 32-layer comparison resolves nothing about the kernels'
    arithmetic: its bound, 5e-2, only records the number and fails a
    result that is far off.  The kernels are checked in the shallow
    phases, which run the same kernels at the same widths, against 1e-4:
    about 6x above the float32 readings on the chip (1.54e-5 at most) and
    about 3x below the 3.2e-4 to 3.6e-4 read there when both engines ran
    at the default matmul precision, which rounds dot inputs to bf16.
    """
    return 1e-4 if layers <= FOLLOW_UP_LAYERS else 5e-2


@dataclasses.dataclass(frozen=True)
class Geometry:
    page: int
    batch: int
    max_len: int
    chunk: int
    prompt_lens: tuple
    max_new: int


FULL = Geometry(PAGE, BATCH, MAX_LEN, CHUNK, PROMPT_LENS, MAX_NEW)
TINY = Geometry(page=4, batch=4, max_len=64, chunk=8, prompt_lens=(8, 40),
                max_new=8)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts XLA compilations (persistent-cache fetches included)."""

    def __init__(self, jax):
        self.n, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.n += 1
            self.secs += duration


def _prompts(rng, geo: Geometry, vocab: int):
    lo, hi = geo.prompt_lens
    lens = rng.integers(lo, hi + 1, geo.batch)
    lens[0], lens[-1] = lo, hi          # both ends of the range, always
    return [rng.integers(0, vocab, int(n)).astype("int32") for n in lens]


def _memory(jax):
    """(bytes in use now, the process's peak so far) on the device."""
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("bytes_in_use"), stats.get("peak_bytes_in_use")


def _spec(jax, tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        tree)


def _rel_err(np, got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


def paged_phase(name, cfg, geo, seed, counter, on_chip, *, kv_dtype,
                spec_k=1):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.serve import (PagedKVCache, PagedLM, Request, Scheduler,
                             static_batch_generate)

    t0, c0, s0 = time.perf_counter(), counter.n, counter.secs
    key = jax.random.PRNGKey(seed)
    prompts = _prompts(np.random.default_rng(seed), geo, cfg.vocab)
    model = PagedLM(cfg, key, kv_dtype=kv_dtype, spec_k=spec_k)

    def fresh():
        return PagedKVCache.create(cfg, batch=geo.batch, max_len=geo.max_len,
                                   page=geo.page, kv_dtype=model.kv_dtype)

    sched = Scheduler(model, fresh(), chunk=geo.chunk)
    for i, p in enumerate(prompts):
        sched.submit(Request(rid=i, prompt=p, max_new=geo.max_new))
    got = sched.run()
    all_terminal = (len(sched.finished) == len(prompts)
                    and all(len(t) == geo.max_new for t in got.values()))
    want = static_batch_generate(model, fresh(), prompts, geo.max_new,
                                 chunk=geo.chunk)
    mismatch = [i for i in range(len(prompts)) if got.get(i) != want[i]]

    # Prefill and decode logits, Pallas engine vs the einsum oracle on the
    # same weights: one batched chunk of every prompt, then one decode step.
    def probe(m):
        cache = fresh()
        n = len(prompts)
        toks = np.zeros((n, geo.chunk), np.int32)
        for i, p in enumerate(prompts):
            cache = cache.allocate(i, cache.pages_for(geo.chunk + 1))
            toks[i] = p[:geo.chunk]
        rows = np.arange(n, dtype=np.int32)
        lp, cache = m.prefill_batch(
            toks, np.full((n,), geo.chunk, np.int32), rows,
            np.zeros((n,), np.int32), cache,
        )
        feed = jnp.asarray([p[-1] for p in prompts], jnp.int32)
        ld, cache = m.decode_step(feed, cache, jnp.ones((n,), bool))
        return (np.asarray(lp, np.float32)[:, :cfg.vocab],
                np.asarray(ld, np.float32)[:, :cfg.vocab])

    ref_model = PagedLM(cfg, key, impl="ref", kv_dtype=kv_dtype,
                        params=model.params)
    with jax.default_matmul_precision("highest"):
        pallas = probe(model)
        ref = probe(ref_model)
    err = {k: _rel_err(np, g, w)
           for k, g, w in zip(("prefill", "decode"), pallas, ref)}
    finite = all(bool(np.isfinite(x).all()) for x in pallas + ref)
    in_use, peak = _memory(jax)     # both engines' weights and pools live

    c = sched.cache
    text = model._decode_many.lower(
        *_spec(jax, (model.params, jnp.zeros((geo.batch,), jnp.int32),
                     c.k_pages, c.v_pages, c.k_scale, c.v_scale,
                     c.page_table, c.lengths, jnp.ones((geo.batch,), bool))),
        n=1,
    ).as_text()
    row = {
        "phase": name,
        "layers": cfg.n_layers,
        "widths": {"d_model": cfg.d_model, "heads": model.h,
                   "kv_heads": model.kvh, "head_dim": model.hd,
                   "vocab": cfg.vocab},
        "kv_dtype": jnp.dtype(model.kv_dtype).name,
        "spec_k": spec_k,
        "requests": len(prompts),
        "prompt_tokens": int(sum(len(p) for p in prompts)),
        "tokens": int(sum(len(t) for t in got.values())),
        "acceptance_rate": (sched.stats.acceptance_rate if spec_k > 1
                            else None),
        "wall_s": time.perf_counter() - t0,
        "compiles": counter.n - c0,
        "compile_s": counter.secs - s0,
        "bytes_in_use": in_use,
        "peak_bytes_in_use": peak,
        "decode_tpu_custom_call": "tpu_custom_call" in text,
        "all_terminal": all_terminal,
        "sched_eq_static": not mismatch,
        "mismatched_requests": mismatch,
        "logits_rel_err": err,
        "logits_tol": logits_tol(cfg.n_layers),
        "finite": finite,
    }
    row["ok"] = (all_terminal and not mismatch and finite
                 and max(err.values()) <= logits_tol(cfg.n_layers)
                 and (row["decode_tpu_custom_call"] or not on_chip))
    return row


def recurrent_phase(name, cfg, geo, seed, counter):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.serve import (RecurrentLM, Request, Scheduler,
                             recurrent_reference_generate)

    t0, c0, s0 = time.perf_counter(), counter.n, counter.secs
    prompts = _prompts(np.random.default_rng(seed), geo, cfg.vocab)
    model = RecurrentLM(cfg, jax.random.PRNGKey(seed))
    sched = Scheduler(model, model.init_pool(geo.batch), chunk=geo.chunk)
    for i, p in enumerate(prompts):
        sched.submit(Request(rid=i, prompt=p, max_new=geo.max_new))
    got = sched.run()
    all_terminal = (len(sched.finished) == len(prompts)
                    and all(len(t) == geo.max_new for t in got.values()))
    pool = model.init_pool(geo.batch)
    want = recurrent_reference_generate(model, pool, prompts, geo.max_new,
                                        chunk=geo.chunk)
    mismatch = [i for i in range(len(prompts)) if got.get(i) != want[i]]
    finite = all(
        bool(jnp.isfinite(t).all())
        for p in (sched.family.pool, pool) for t in p.tensors.values()
    )
    in_use, peak = _memory(jax)     # weights and both pools live
    state = sched.family.pool.tensors
    row = {
        "phase": name,
        "layers": cfg.n_layers,
        "widths": {"d_model": cfg.d_model, "d_ff": cfg.d_ff,
                   "vocab": cfg.vocab},
        "state_rows": {k: list(v.shape[2:]) for k, v in state.items()},
        "requests": len(prompts),
        "prompt_tokens": int(sum(len(p) for p in prompts)),
        "tokens": int(sum(len(t) for t in got.values())),
        "wall_s": time.perf_counter() - t0,
        "compiles": counter.n - c0,
        "compile_s": counter.secs - s0,
        "bytes_in_use": in_use,
        "peak_bytes_in_use": peak,
        "all_terminal": all_terminal,
        "sched_eq_static": not mismatch,
        "mismatched_requests": mismatch,
        "finite": finite,
    }
    row["ok"] = all_terminal and not mismatch and finite
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="smoke_config sizes on any backend (CPU rehearsal)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    from repro.configs import get_config, smoke_config
    from repro.runtime.device import describe_devices, use_compile_cache

    use_compile_cache()
    device = describe_devices()
    if not args.tiny and device["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{device['platform']!r}); --tiny runs the CPU rehearsal",
              file=sys.stderr)
        return 2
    counter = CompileCounter(jax)
    # On the chip every kernel must be a Mosaic kernel, never interpreted.
    on_chip = device["platform"] == "tpu"
    geo = TINY if args.tiny else FULL
    config = smoke_config if args.tiny else get_config

    def cut(name, layers):
        cfg = config(name)
        return dataclasses.replace(cfg, n_layers=min(layers, cfg.n_layers))

    yi = config("yi-6b")
    phases = [
        lambda: paged_phase("yi-6b/bf16", yi, geo, args.seed, counter, on_chip,
                            kv_dtype="bf16"),
        lambda: paged_phase("yi-6b/int8", cut("yi-6b", FOLLOW_UP_LAYERS), geo,
                            args.seed, counter, on_chip, kv_dtype="int8"),
        lambda: paged_phase("yi-6b/spec4", cut("yi-6b", FOLLOW_UP_LAYERS),
                            geo, args.seed, counter, on_chip, kv_dtype="bf16",
                            spec_k=SPEC_K),
        lambda: recurrent_phase("rwkv6-3b", cut("rwkv6-3b", FOLLOW_UP_LAYERS),
                                geo, args.seed, counter),
    ]
    ok = True
    for run in phases:
        row = run()
        published = get_config(row["phase"].split("/")[0]).n_layers
        row["depth_cut"] = (None if row["layers"] == published
                            else f"{published} -> {row['layers']} layers")
        print(json.dumps(row), flush=True)
        ok = ok and row["ok"]
        gc.collect()     # release this phase's weights and pools
    if not ok:
        print("chip_smoke: a check failed (see the phase lines)",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
