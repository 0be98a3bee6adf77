"""What the per-layer metric readers share.

Each file ``bench/metrics/<name>.py`` defines ``read(run)``, returning one
number or ``None`` when the traced run holds nothing to read.  ``run`` has:

* ``trace``: the reduced trace (:class:`bench.trace.Trace`) of the first
  seconds of the window;
* ``calls``: one record per family call the harness wrapped, by index
  (``kind``; for a decode, ``n`` and the active rows' lengths ``lens``
  before it; for a prefill, the real rows' ``counts``, ``starts`` and how
  many rows it ``completing``);
* ``cfg`` and ``fam``: the configuration and its family module (work
  counts); ``peaks``: the device's row of ``bench/peaks.json`` (``None``
  off the chip); ``compiles_in_window``.
"""
from __future__ import annotations

import importlib.util
import os
from typing import List, Optional

import numpy as np

from bench.trace import overlap

HERE = os.path.dirname(os.path.abspath(__file__))


def sibling(name: str):
    """The metric module ``bench/metrics/<name>.py``."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def traced_calls(run, kind: str) -> List[int]:
    """Indices of the ``kind`` calls that ran programs in the window."""
    return sorted({m.call for m in run.trace.modules
                   if m.kind == kind and m.call is not None})


def program_ms(run, kind: str) -> float:
    return run.trace.module_ns(kind) / 1e6


def decode_steps(run) -> int:
    return sum(run.calls[i]["n"] for i in traced_calls(run, "decode_steps"))


def decode_lens(run):
    """Per traced decode step: the live lengths of its active rows, the
    step's own token included."""
    for i in traced_calls(run, "decode_steps"):
        c = run.calls[i]
        for s in range(c["n"]):
            yield c["lens"] + s + 1


def prefill_rows(run):
    for i in traced_calls(run, "prefill_batch"):
        c = run.calls[i]
        yield c["starts"], c["counts"], c["completing"]


def decode_flops(run) -> float:
    """Model FLOPs of the traced decode steps, logits included."""
    fam, cfg = run.fam, run.cfg
    tot = 0.0
    for lens in decode_lens(run):
        tot += fam.token_flops(cfg, lens) + lens.size * fam.head_flops(cfg)
    return tot


def prefill_flops(run) -> float:
    """Model FLOPs of the traced prefill rows: every prompt token, and the
    logits of each row that completes its prompt."""
    fam, cfg = run.fam, run.cfg
    tot = 0.0
    for starts, counts, completing in prefill_rows(run):
        ctx = np.concatenate([s + np.arange(c) + 1
                              for s, c in zip(starts, counts)]) \
            if len(counts) else np.zeros((0,))
        tot += fam.token_flops(cfg, ctx) + completing * fam.head_flops(cfg)
    return tot


def roofline_pct(run, cost, time_ns: float) -> Optional[float]:
    """Least time over measured time, in %, for a kernel whose calls cost
    ``cost`` = [(flops, bytes), ...]."""
    if run.peaks is None or time_ns <= 0 or not cost:
        return None
    least = sum(max(f / run.peaks["bf16_flops_s"], b / run.peaks["hbm_bytes_s"])
                for f, b in cost)
    return 100.0 * least / (time_ns / 1e9)


def host_ms_per_step(run) -> Optional[float]:
    """Mean host time of ``Scheduler.step`` outside the family's calls."""
    tr = run.trace
    steps = [sp for sp in tr.spans if sp.name == "bench.step"
             and sp.start >= tr.t0 and sp.end <= tr.t1]
    if not steps:
        return None
    calls = [sp for sp in tr.spans if sp.name[len("bench."):] in
             ("prefill_batch", "decode_steps", "verify_steps")]
    tot = 0.0
    for st in steps:
        inner = sum(c.end - c.start for c in calls
                    if c.start >= st.start and c.end <= st.end)
        tot += st.end - st.start - inner
    return tot / len(steps) / 1e6


def idle_pct(run) -> Optional[float]:
    """Share of the traced window with no op on the device, leaving out
    the time the harness waited with no request queued or resident."""
    tr = run.trace
    if not tr.ops:
        return None
    waits = [(max(sp.start, tr.t0), min(sp.end, tr.t1)) for sp in tr.spans
             if sp.name == "bench.idle_wait" and sp.end > tr.t0
             and sp.start < tr.t1]
    gaps = tr.idle_gaps()
    idle = sum(e - s for s, e in gaps) - overlap(gaps, waits)
    span = tr.t1 - tr.t0 - sum(e - s for s, e in waits)
    return 100.0 * idle / span if span > 0 else None
