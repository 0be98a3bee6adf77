"""The program's host spans (``repro.runtime.tracing``) in a profiler trace.

Each serving test drives the ``Scheduler`` inside a profiler window (CPU,
``impl='ref'``) and reads the window's host events back: every span name is
written, each span lies inside the phase that opens it, and the stats agree
with the scheduler's own counters.  Nothing here is timed.
"""
import gc
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.kernels.paged_decode import (
    MAX_PAGES_PER_BLOCK,
    VMEM_BUDGET,
    pages_per_block,
)
from repro.runtime import tracing
from repro.runtime.tracing import SPANS, install_gc_spans
from repro.serve import (
    PagedKVCache,
    PagedLM,
    RecurrentLM,
    Request,
    Scheduler,
)
from repro.serve.paged_lm import _block_fill

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src", "repro")

#: The phases of one ``sched.step``, in the order it opens them.
PHASES = ("sched.admit", "sched.prefill", "sched.decode", "sched.retire")

#: Where each span may be opened: the spans it lies inside.
PARENTS = {
    "sched.admit": {"sched.step"},
    "sched.prefill": {"sched.step"},
    "sched.decode": {"sched.step"},
    "sched.retire": {"sched.step"},
    "sched.grow": {"sched.decode"},
    "sched.handoff": {"sched.prefill", "sched.decode"},
    "engine.launch": {"sched.prefill", "sched.decode"},
    "engine.sync": {"sched.prefill", "sched.decode"},
    "engine.reset": {"sched.admit"},
}


def _profile(tmp_path, fn):
    """Run ``fn`` inside a profiler window; returns its spans as
    ``(name, start_ns, end_ns, stats)``."""
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
    pd = ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for pl in pd.planes if pl.name == "/host:CPU"
            for ln in pl.lines for e in ln.events if e.name in SPANS]


def _named(spans, name):
    return [sp for sp in spans if sp[0] == name]


def _paged(spec_k):
    cfg = smoke_config("yi-6b")
    model = PagedLM(cfg, jax.random.PRNGKey(0), impl="ref", spec_k=spec_k)
    cache = PagedKVCache.create(cfg, batch=2, max_len=32, page=4)
    # Prefix sharing makes the admission map shared pages too.
    return cfg, Scheduler(model, cache, chunk=4,
                          prefix_sharing=spec_k == 1)


def _recurrent(spec_k):
    cfg = smoke_config("rwkv6-3b")
    model = RecurrentLM(cfg, jax.random.PRNGKey(0))
    return cfg, Scheduler(model, model.init_pool(2), chunk=4)


def _serve(make, spec_k):
    """A scheduler and the requests it will serve: two share a page-aligned
    prefix, three compete for two slots, and decode crosses pages."""
    cfg, sched = make(spec_k)
    rng = np.random.default_rng(0)
    system = rng.integers(0, cfg.vocab, 8)
    prompts = [np.concatenate([system, rng.integers(0, cfg.vocab, 3)]),
               np.concatenate([system, rng.integers(0, cfg.vocab, 2)]),
               rng.integers(0, cfg.vocab, 6)]
    reqs = [Request(rid=i, prompt=p.astype(np.int32), max_new=7)
            for i, p in enumerate(prompts)]
    for r in reqs:
        sched.submit(r)
    return sched, reqs


@pytest.mark.parametrize("make,spec_k", [(_paged, 1), (_paged, 2),
                                         (_recurrent, 1)],
                         ids=["paged", "speculative", "recurrent"])
def test_spans_of_a_served_run(tmp_path, make, spec_k):
    sched, reqs = _serve(make, spec_k)
    spans = _profile(tmp_path, sched.run)
    assert {sp[0] for sp in spans} >= set(SPANS) - {"gc"}

    for name, s, e, _ in spans:
        parents = PARENTS.get(name)
        if parents:
            assert any(p[0] in parents and p[1] <= s and e <= p[2]
                       for p in spans), f"{name} at {s} outside {parents}"

    steps = _named(spans, "sched.step")
    assert [sp[3]["step"] for sp in steps] == list(range(1, sched._step + 1))
    # Each step opens every phase once, in order.
    for _, s, e, _ in steps:
        phases = sorted((ps, name) for name, ps, pe, _ in spans
                        if name in PHASES and s <= ps and pe <= e)
        assert [name for _, name in phases] == list(PHASES)

    st = sched.stats
    launches = _named(spans, "engine.launch")
    decoded = sum(sp[3]["n"] for sp in launches
                  if sp[3]["kind"] in ("decode", "verify"))
    assert decoded == st.decode_steps
    assert sum(sp[3]["n"] for sp in launches
               if sp[3]["kind"] == "verify") == st.spec_steps
    prefills = [sp[3]["rows"] for sp in launches
                if sp[3]["kind"] == "prefill"]
    assert len(prefills) == st.prefill_steps and all(prefills)
    assert st.tokens == sum(len(r.generated) for r in reqs)
    # One wait per prefill at most: the family's, or the scheduler's read
    # of device logits, never both.
    for _, s, e, _ in _named(spans, "sched.prefill"):
        assert sum(s <= sp[1] and sp[2] <= e
                   for sp in _named(spans, "engine.sync")) <= 1
    kinds = {sp[3]["kind"] for sp in launches}
    assert kinds == ({"prefill", "verify"} if spec_k > 1
                     else {"prefill", "decode"})
    assert (st.share_events > 0) == (make is _paged and spec_k == 1)


@pytest.mark.parametrize("make", [_paged, _recurrent],
                         ids=["paged", "recurrent"])
def test_each_admission_opens_one_reset_span(tmp_path, make):
    sched, reqs = _serve(make, 1)
    resets = _named(_profile(tmp_path, sched.run), "engine.reset")
    assert len(resets) == sched._admit_counter == len(reqs)
    assert all(sp[3]["slots"] == 1 for sp in resets)


def test_recurrent_launches_carry_their_row_fill(tmp_path):
    """Each recurrent ``engine.launch`` carries ``row_fill``: for prefill
    the real tokens over the call's rows x chunk, for decode the active
    rows over the batch."""
    sched, _ = _serve(_recurrent, 1)
    fam = sched.family
    want = []
    prefill, decode = fam.prefill_batch, fam.decode_steps

    def prefill_batch(tokens, counts, slots, starts):
        want.append(("prefill", counts.sum() / tokens.size))
        return prefill(tokens, counts, slots, starts)

    def decode_steps(tokens, active, n):
        # One launch per power of two in n, each with the same rows.
        want.extend([("decode", active.sum() / fam.batch)]
                    * bin(int(n)).count("1"))
        return decode(tokens, active, n)

    fam.prefill_batch, fam.decode_steps = prefill_batch, decode_steps
    launches = _named(_profile(tmp_path, sched.run), "engine.launch")
    got = [(sp[3]["kind"], sp[3]["row_fill"]) for sp in launches]
    assert [k for k, _ in got] == [k for k, _ in want]
    assert [f for _, f in got] == pytest.approx([f for _, f in want])
    assert any(0 < f < 1 for _, f in got)


#: (page, KV heads, head dim, table width) of the longctx cell, of
#: ``chip_smoke.py`` and of the benchmark's CPU rehearsal.
CELL, SMOKE, REHEARSAL = (16, 4, 128, 256), (16, 4, 128, 40), (16, 2, 32, 16)


@pytest.mark.parametrize("kv", [jnp.bfloat16, jnp.int8, jnp.float32])
@pytest.mark.parametrize("shape", [CELL, SMOKE, REHEARSAL],
                         ids=["cell", "smoke", "rehearsal"])
def test_pages_per_block_fits_its_vmem_and_its_table(shape, kv):
    page, kvh, d, n_pages = shape
    quantized = kv == jnp.int8
    ppb = pages_per_block(page, kvh, d, kv, n_pages, quantized)
    assert ppb & (ppb - 1) == 0 and 1 < ppb <= min(MAX_PAGES_PER_BLOCK,
                                                   n_pages)
    # Two buffers of K and V (and scale) pages; a token's (KVH, D) tile
    # pads to (8 * 4 / itemsize, 128), a scale page to 128 lanes.
    item = jnp.dtype(kv).itemsize
    sub = 8 * 4 // item
    tile = -(-kvh // sub) * sub * -(-d // 128) * 128 * item
    per_page = page * tile + (page * 128 * 4 if quantized else 0)
    assert 2 * 2 * ppb * per_page <= VMEM_BUDGET
    if shape == CELL:
        assert ppb == 16                 # 256 tokens, 512 KiB of K+V rows


def test_decode_launches_carry_the_kernels_block_walk(tmp_path):
    """Each decode ``engine.launch`` names the decode kernel's pages per
    block and how full the active rows' walked blocks are, from the host
    lengths at the launch's first step."""
    sched, _ = _serve(_paged, 1)
    model, cache = sched.family.model, sched.family.cache
    ppb = pages_per_block(cache.page_size, model.kvh, model.hd,
                          cache.k_pages.dtype, cache.pages_per_seq)
    launches = [sp[3] for sp in _named(_profile(tmp_path, sched.run),
                                       "engine.launch")]
    decode = [st for st in launches if st["kind"] == "decode"]
    assert decode and all(st["ppb"] == ppb for st in decode)
    assert all(0 < st["block_fill"] <= 1 for st in decode)
    assert not any("ppb" in st or "block_fill" in st for st in launches
                   if st["kind"] != "decode")


@pytest.mark.parametrize("lengths,active,fill", [
    ([5, 0, 9], [True, False, True], (2 + 3) / (2 * (1 + 2))),
    ([16, 17, 0], [True, True, False], (4 + 5) / (2 * (2 + 3))),
    ([3, 7], [False, False], 0.0),
])
def test_block_fill_counts_live_pages_over_walked_slots(lengths, active,
                                                        fill):
    assert _block_fill(np.asarray(lengths), np.asarray(active), 4, 2) \
        == pytest.approx(fill)
    assert _block_fill(None, np.asarray(active), 4, 2) == 0.0


def test_gc_span_per_collection_of_the_older_generations(tmp_path):
    install_gc_spans()
    install_gc_spans()
    assert gc.callbacks.count(tracing._GC_SPANS) == 1

    def collect():
        was = gc.isenabled()
        gc.disable()                    # no collection but the two below
        try:
            ring = [[] for _ in range(100)]
            for a, b in zip(ring, ring[1:] + ring[:1]):
                a.append(b)             # a reference cycle only gc frees
            del ring, a, b
            gc.collect(2)
            gc.collect(0)
        finally:
            if was:
                gc.enable()

    spans = _profile(tmp_path, collect)
    got = _named(spans, "gc")
    assert len(got) == 1
    assert got[0][3]["generation"] == 2
    assert got[0][3]["collected"] >= 100


def test_every_span_the_program_opens_is_listed():
    opened = set()
    for path in glob.glob(os.path.join(SRC, "**", "*.py"), recursive=True):
        with open(path) as f:
            opened |= set(re.findall(r'\bspan\(\s*"([^"]+)"', f.read()))
    assert opened == set(SPANS)
