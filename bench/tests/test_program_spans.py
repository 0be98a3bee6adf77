"""The program's own spans (``repro.runtime.tracing``) in the benchmark's
traces.

``data/longctx-trace-spans/`` is a half-second traced window of
``yi6b-attn-l4.longctx`` on one TPU v5e, recorded with ``record_trace.py``
from a tree whose scheduler and engines write those spans (some with stats
that the program no longer writes; nothing here reads them).  The harness's
reduction (``bench/trace.py``) keeps only its own ``bench.*`` spans; the
tests here read the program's spans from the same profile, split the
device's idle gaps by them, and pin what that split reads.  They also pin
the nine per-layer readers on the older ``data/longctx-trace/``, which was
recorded before the program had spans.
"""
import dataclasses
import glob
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from bench import trace
from bench.families import paged_attn
from bench.readers import sibling
from conftest import ROOT

DATA = os.path.join(ROOT, "bench", "tests", "data")
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CFG = json.load(open(os.path.join(ROOT, "bench", "configs",
                                  "yi6b-attn-l4.json")))


def _spans_named():
    from repro.runtime.tracing import SPANS
    return SPANS


def load(path):
    """The harness's reduction of ``path``, with the program's spans added
    to its ``bench.*`` ones."""
    from jax.profiler import ProfileData

    tr = trace.load(path)
    names = set(_spans_named())
    pd = ProfileData.from_file(path)
    prog = [trace.Span(e.name, e.start_ns, e.start_ns + e.duration_ns,
                       dict(e.stats))
            for pl in pd.planes if pl.name == "/host:CPU"
            for ln in pl.lines for e in ln.events if e.name in names]
    return dataclasses.replace(tr, spans=tr.spans + prog)


def named(tr, name):
    return [sp for sp in tr.spans if sp.name == name]


def inner_gaps(tr):
    """Idle gaps that neither the window's start nor its end cuts short."""
    return [(s, e) for s, e in tr.idle_gaps() if s > tr.t0 and e < tr.t1]


def account_ms_per_step(tr):
    """Mean host time in ``sched.account`` per ``sched.step`` of the
    window."""
    steps = [sp for sp in named(tr, "sched.step")
             if sp.start >= tr.t0 and sp.end <= tr.t1]
    acc = named(tr, "sched.account")
    if not steps:
        return None
    tot = sum(a.end - a.start for st in steps for a in acc
              if st.start <= a.start and a.end <= st.end)
    return tot / len(steps) / 1e6


def sync_split(tr):
    """Each idle gap that opens inside an ``engine.sync`` span and outlasts
    it, as (its start, the sync's close, its end) in ns: from the start to
    the close the result reaches the host, from the close to the end the
    host prepares and launches the next program."""
    syncs = named(tr, "engine.sync")
    out = []
    for s, e in inner_gaps(tr):
        owner = [sp for sp in syncs if sp.start <= s < sp.end < e]
        if owner:
            out.append((s, owner[0].end, e))
    return out


def sync_tail_ms(tr):
    """Mean, over the ``engine.sync`` spans that close with the device
    idle, of the time from its last op to the close."""
    gaps = inner_gaps(tr)
    tails = [sp.end - s for sp in named(tr, "engine.sync")
             for s, e in gaps if s <= sp.end < e]
    return float(np.mean(tails)) / 1e6 if tails else None


def relaunch_ms(tr):
    split = sync_split(tr)
    return float(np.mean([e - c for _, c, e in split])) / 1e6 \
        if split else None


def _run(tr, data):
    calls = json.load(open(os.path.join(data, "calls.json")))
    for c in calls:
        for k in ("lens", "counts", "starts"):
            if k in c:
                c[k] = np.asarray(c[k], np.int64)
    peaks = json.load(open(os.path.join(ROOT, "bench", "peaks.json")))
    return SimpleNamespace(trace=tr, calls=calls, cfg=CFG, fam=paged_attn,
                           peaks=peaks["TPU v5 lite"], compiles_in_window=0)


def _xplane(data):
    return glob.glob(os.path.join(data, "**", "*.xplane.pb"),
                     recursive=True)[0]


# -- the older fixture: the nine readers, pinned ---------------------------

#: What each reader read on ``data/longctx-trace/`` when it was committed.
PINNED = {
    "jit.compiles_in_window": 0,
    "kv_append.us_per_layer_step": 5.3886,
    "sched.host_ms_per_step": 8.7377134,
    "decode.step_ms": 79.1360478,
    "prefill.ms_per_ktok": 75.90010089686098,
    "paged_decode_roofline": 2.949179443884268,
    "paged_prefill_roofline": 1.9820630079987047,
    "device.idle_pct": 3.9668563554056457,
    "step_mfu": 0.9446060660378972,
}


def test_the_nine_readers_read_what_they_read_when_committed():
    data = os.path.join(DATA, "longctx-trace")
    run = _run(trace.load(_xplane(data)), data)
    assert set(PINNED) == {m["name"] for m in BENCH["per_layer"]}
    for name, value in PINNED.items():
        assert sibling(name).read(run) == pytest.approx(value, rel=1e-12), \
            name


# -- the chip fixture with program spans -------------------------------------

SPANS_DATA = os.path.join(DATA, "longctx-trace-spans")


@pytest.fixture(scope="module")
def chip():
    tr = load(_xplane(SPANS_DATA))
    return tr, _run(tr, SPANS_DATA)


def test_chip_fixture_holds_every_scheduler_and_engine_span(chip):
    tr, _ = chip
    assert {sp.name for sp in tr.spans} >= set(_spans_named()) - {"gc"}
    assert os.path.getsize(_xplane(SPANS_DATA)) < 2e6


def test_longest_gaps_are_named_by_program_spans(chip):
    tr, _ = chip
    for name, _ in tr.top_gaps(10):
        assert name in _spans_named(), name
    # Every gap of a millisecond or more that the window does not cut opens
    # while the host waits in engine.sync.
    syncs = named(tr, "engine.sync")
    long = [(s, e) for s, e in inner_gaps(tr) if e - s >= 1e6]
    assert len(long) == 3
    for s, _ in long:
        assert any(sp.start <= s < sp.end for sp in syncs)


def test_the_program_span_readings_on_the_chip_fixture(chip):
    tr, _ = chip
    assert account_ms_per_step(tr) == pytest.approx(0.64530425, rel=1e-9)
    assert sync_tail_ms(tr) == pytest.approx(2.4789856666666665, rel=1e-9)
    assert relaunch_ms(tr) == pytest.approx(0.8808876666666666, rel=1e-9)


def test_sync_tail_and_relaunch_split_the_gaps_they_share(chip):
    tr, _ = chip
    split = sync_split(tr)
    assert len(split) == 3
    tails = []
    for s, close, e in split:
        assert s <= close < e
        tails.append(close - s)
    # Every sync that closes with the device idle opened such a gap.
    assert sync_tail_ms(tr) == pytest.approx(np.mean(tails) / 1e6)


def test_kernels_are_found_once_per_layer_and_call_on_the_chip_fixture(chip):
    tr, run = chip
    layers = CFG["num_hidden_layers"]
    steps = sum(run.calls[i]["n"] for i in
                {m.call for m in tr.modules if m.kind == "decode_steps"})
    prefills = len({m.call for m in tr.modules if m.kind == "prefill_batch"})
    decode = sibling("paged_decode_roofline").is_kernel
    prefill = sibling("paged_prefill_roofline").is_kernel
    append = sibling("kv_append.us_per_layer_step").is_kernel
    assert steps and prefills
    assert tr.op_calls("decode_steps", decode) == layers * steps
    assert tr.op_calls("decode_steps", append) == 2 * layers * steps
    assert tr.op_calls("prefill_batch", prefill) == layers * prefills
    assert tr.op_calls("prefill_batch", decode) == 0
    # The kernels and programs carry their names into the trace.
    for kind, match, name in (("decode_steps", decode, "%paged_decode."),
                              ("decode_steps", append, "%indirect_scatter."),
                              ("prefill_batch", prefill, "%paged_prefill.")):
        assert all(trace.short_name(t).startswith(name)
                   for t, _, _, m in tr.ops
                   if m >= 0 and tr.modules[m].kind == kind and match(t))
    assert {m.name.split("(")[0] for m in tr.modules} == {
        "jit_paged_lm_decode_steps", "jit_paged_lm_prefill_batch"}


# -- a CPU rehearsal: every span, and the launch stats against the calls ----


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    import subprocess
    import sys

    out = tmp_path_factory.mktemp("spans")
    cell = BENCH["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "tests",
                                      "record_trace.py"),
         str(out), "1", "--workload", cell, "--seed", "3000000013",
         "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return load(_xplane(str(out))), json.load(open(out / "calls.json"))


def test_rehearsal_trace_holds_every_span_but_gc(rehearsal):
    tr, _ = rehearsal
    assert {sp.name for sp in tr.spans} >= set(_spans_named()) - {"gc"}
    sched = [sp for sp in tr.spans if sp.name.startswith("sched.")]
    for sp in tr.spans:
        if sp.name.startswith("engine."):
            assert any(p.start <= sp.start and sp.end <= p.end
                       for p in sched), sp


def test_launch_stats_match_the_harness_calls(rehearsal):
    tr, calls = rehearsal
    launches = named(tr, "engine.launch")
    seen = 0
    for kind in trace.CALL_KINDS:
        for call in named(tr, f"bench.{kind}"):
            inside = [sp.stats for sp in launches
                      if call.start <= sp.start and sp.end <= call.end]
            rec = calls[call.stats["call"]]
            if kind == "prefill_batch":
                assert [s["rows"] for s in inside] == [len(rec["counts"])]
            else:
                assert sum(s["n"] for s in inside) == rec["n"]
            seen += 1
    assert seen
