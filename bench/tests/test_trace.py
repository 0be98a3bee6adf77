"""The trace reduction, on a short trace recorded on one TPU v5e.

``data/longctx-trace/`` holds the profile of a ``--trace 1`` run of
``yi6b-attn-l4.longctx`` with a half-second window (the ``.xplane.pb`` and
the harness's ``calls.json``).  Each number the reduction gives is checked
against a plain recount from the raw events.
"""
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

DATA = os.path.join(ROOT, "bench", "tests", "data", "longctx-trace")


@pytest.fixture(scope="module")
def raw():
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(DATA, "*.xplane.pb"))[0]
    return path, ProfileData.from_file(path)


@pytest.fixture(scope="module")
def tr(raw):
    return trace.load(raw[0])


@pytest.fixture(scope="module")
def run(tr):
    cfg = json.load(open(os.path.join(ROOT, "bench", "configs",
                                      "yi6b-attn-l4.json")))
    calls = json.load(open(os.path.join(DATA, "calls.json")))
    for c in calls:
        for k in ("lens", "counts", "starts"):
            if k in c:
                c[k] = np.asarray(c[k], np.int64)
    peaks = json.load(open(os.path.join(ROOT, "bench", "peaks.json")))
    return SimpleNamespace(trace=tr, calls=calls, cfg=cfg, fam=paged_attn,
                           peaks=peaks["TPU v5 lite"], compiles_in_window=0)


def _device_events(pd, line):
    dev = [p for p in pd.planes if p.name == "/device:TPU:0"][0]
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for ln in dev.lines if ln.name == line for e in ln.events]


def test_window_is_the_traced_span(raw, tr):
    _, pd = raw
    host = [p for p in pd.planes if p.name == "/host:CPU"][0]
    marks = [e for ln in host.lines for e in ln.events
             if e.name == "bench.traced"]
    assert len(marks) == 1
    assert tr.t0 == marks[0].start_ns
    assert tr.window_s == pytest.approx(marks[0].duration_ns / 1e9)


def test_busy_and_idle_partition_the_window(raw, tr):
    _, pd = raw
    # Recount busy time on a 1 us grid.
    grid = np.zeros(int((tr.t1 - tr.t0) // 1000) + 1, bool)
    for _, s, e, _ in _device_events(pd, "XLA Ops"):
        lo = max(int((s - tr.t0) // 1000), 0)
        hi = min(int(np.ceil((e - tr.t0) / 1000)), grid.size)
        if hi > lo:
            grid[lo:hi] = True
    assert tr.busy_s() == pytest.approx(grid.sum() * 1e-6, rel=0.02)
    idle = sum(e - s for s, e in tr.idle_gaps()) / 1e9
    assert idle + tr.busy_s() == pytest.approx(tr.window_s, rel=1e-9)
    assert 0 < tr.busy_s() < tr.window_s


def test_self_times_add_up_to_busy(tr):
    # One device; ops nest (a while covers its body), never overlap.
    assert tr.self_ns.sum() / 1e9 == pytest.approx(tr.busy_s(), rel=1e-6)


def test_each_program_belongs_to_the_call_that_enqueued_it(raw, tr):
    _, pd = raw
    host = [p for p in pd.planes if p.name == "/host:CPU"][0]
    enq = {}
    for ln in host.lines:
        for e in ln.events:
            if e.name == "DoEnqueueProgram":
                enq.setdefault(int(dict(e.stats)["run_id"]), e.start_ns)
    spans = [sp for sp in tr.spans if sp.name in
             ("bench.prefill_batch", "bench.decode_steps")]
    assert tr.modules
    for m in tr.modules:
        t = enq[m.run_id]
        owner = [sp for sp in spans
                 if sp.start <= t < sp.end + trace.ENQUEUE_SLACK_NS]
        assert len(owner) == 1
        assert m.kind == owner[0].name[len("bench."):]
        assert m.call == owner[0].stats["call"]
    kinds = {m.kind for m in tr.modules}
    assert kinds == {"prefill_batch", "decode_steps"}


def test_kernels_are_found_once_per_layer_and_call(tr, run):
    layers = run.cfg["num_hidden_layers"]
    steps = sum(run.calls[i]["n"] for i in
                {m.call for m in tr.modules if m.kind == "decode_steps"})
    prefills = len({m.call for m in tr.modules if m.kind == "prefill_batch"})
    decode = sibling("paged_decode_roofline").is_kernel
    prefill = sibling("paged_prefill_roofline").is_kernel
    append = sibling("kv_append.us_per_layer_step").is_kernel
    assert tr.op_calls("decode_steps", decode) == layers * steps
    assert tr.op_calls("decode_steps", append) == 2 * layers * steps
    assert tr.op_calls("prefill_batch", prefill) == layers * prefills
    assert tr.op_calls("prefill_batch", decode) == 0


def test_readers_match_a_recount(raw, tr, run):
    _, pd = raw
    mods = _device_events(pd, "XLA Modules")
    decode_ns = sum(e - s for _, s, e, st in mods
                    if any(m.run_id == int(st["run_id"])
                           and m.kind == "decode_steps" for m in tr.modules))
    steps = sum(run.calls[i]["n"] for i in
                {m.call for m in tr.modules if m.kind == "decode_steps"})
    got = sibling("decode.step_ms").read(run)
    assert got == pytest.approx(decode_ns / 1e6 / steps)
    for name in ("paged_decode_roofline", "paged_prefill_roofline",
                 "step_mfu", "device.idle_pct"):
        v = sibling(name).read(run)
        assert v is not None and 0 < v <= 100, name
