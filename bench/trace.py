"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics read.

What the trace holds today, on a TPU v5e with JAX 0.9 (no ``name=`` on any
``pallas_call``, no ``named_scope`` in the program):

* ``/device:TPU:<i>`` planes.  Line ``XLA Modules``: one event per program
  execution, named ``jit_<fn>(<fingerprint>)`` (``jit__unknown(...)`` for
  programs jitted from a ``functools.partial``), with a ``run_id`` stat.
  Line ``XLA Ops``: one event per HLO instruction execution, named by the
  instruction's text (``%closed_call.93 = f32[64,32,128]{...}
  custom-call(...), custom_call_target="tpu_custom_call", ...``); ops nest
  (a ``while`` covers the ops of its body).
* the ``/host:CPU`` plane.  The benchmark's ``TraceAnnotation`` spans
  (``bench.*``, with their keyword stats), and the runtime's
  ``DoEnqueueProgram`` events, which carry the ``run_id`` of the program
  they enqueue, at the host time of the launch.

Timestamps of both planes are on one clock, in nanoseconds.  A program
execution belongs to the family call (span ``bench.<kind>``) that was open,
or had closed less than ``ENQUEUE_SLACK_NS`` before, when its ``run_id`` was
enqueued; when no enqueue event names it, to the call open when it started
on the device.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

#: The family calls the harness wraps in spans, by kind.
CALL_KINDS = ("prefill_batch", "decode_steps", "verify_steps")
_OP_NAME = re.compile(r"^(%[\w.\-]+)")
#: How long after a call's span its programs may still be enqueued.
ENQUEUE_SLACK_NS = 1e6


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    stats: Dict[str, object]


@dataclasses.dataclass
class Module:
    name: str
    start: float
    end: float
    run_id: Optional[int]
    kind: str = "other"          # prefill_batch | decode_steps | ... | other
    call: Optional[int] = None   # the harness's index of that call


@dataclasses.dataclass
class Trace:
    """One traced window on the device(s) it saw."""

    t0: float
    t1: float
    devices: int
    ops: List[Tuple[str, float, float, int]]   # (text, start, end, module)
    self_ns: np.ndarray                        # per op: time not in a child
    modules: List[Module]
    spans: List[Span]

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def busy_s(self) -> float:
        """Seconds in which any op ran, averaged over the devices."""
        return _union([(s, e) for _, s, e, _ in self.ops], self.t0,
                      self.t1) / 1e9 / max(self.devices, 1)

    def idle_gaps(self) -> List[Tuple[float, float]]:
        """(start, end) of each stretch of the window with no op running."""
        iv = sorted((max(s, self.t0), min(e, self.t1))
                    for _, s, e, _ in self.ops if e > self.t0 and s < self.t1)
        gaps, cur = [], self.t0
        for s, e in iv:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if self.t1 > cur:
            gaps.append((cur, self.t1))
        return gaps

    def host_activity(self, t: float) -> str:
        """The innermost ``bench.*`` span open on the host at ``t``."""
        best = None
        for sp in self.spans:
            if sp.start <= t < sp.end and (best is None
                                           or sp.start >= best.start):
                best = sp
        return best.name if best else "no span"

    def module_ns(self, kind: str) -> float:
        return sum(m.end - m.start for m in self.modules if m.kind == kind)

    def op_ns(self, kind: str, match: Callable[[str], bool]) -> float:
        """Self time of the ops that ``match`` inside ``kind`` programs."""
        tot = 0.0
        for i, (text, _, _, m) in enumerate(self.ops):
            if m >= 0 and self.modules[m].kind == kind and match(text):
                tot += self.self_ns[i]
        return tot

    def op_calls(self, kind: str, match: Callable[[str], bool]) -> int:
        return sum(1 for text, _, _, m in self.ops
                   if m >= 0 and self.modules[m].kind == kind and match(text))

    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` ops with most self time, each named by its program's
        kind and its instruction name, with seconds."""
        tot: Dict[str, float] = {}
        for i, (text, _, _, m) in enumerate(self.ops):
            mk = self.modules[m].kind if m >= 0 else "other"
            key = f"{mk}:{short_name(text)}"
            tot[key] = tot.get(key, 0.0) + self.self_ns[i]
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in top]

    def top_gaps(self, n: int = 10) -> List[List]:
        """The ``n`` longest idle stretches, each named by the host span
        open at its middle, with seconds."""
        gaps = sorted(self.idle_gaps(), key=lambda g: g[0] - g[1])[:n]
        return [[self.host_activity((s + e) / 2), (e - s) / 1e9]
                for s, e in gaps]


def short_name(text: str) -> str:
    """``%closed_call.93`` from an op's instruction text."""
    m = _OP_NAME.match(text)
    return m.group(1) if m else text[:40]


def is_pallas(text: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in text


def scalar_operands(text: str) -> int:
    """How many ``s32`` operands lead a custom call's operand list."""
    if "custom-call(" not in text:
        return 0
    n = 0
    for arg in text.split("custom-call(", 1)[1].split(", "):
        if not arg.startswith("s32["):
            break
        n += 1
    return n


def overlap(a, b) -> float:
    """Total overlap of two lists of disjoint (start, end) intervals."""
    a, b = sorted(a), sorted(b)
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def _union(iv, lo: float, hi: float) -> float:
    tot, cur = 0.0, lo
    for s, e in sorted(iv):
        s, e = max(s, cur), min(e, hi)
        if e > s:
            tot += e - s
            cur = e
    return tot


def _self_times(ops) -> np.ndarray:
    """Per op, its duration less the time of the ops nested in it (same
    device: ops are given per device, sorted by start)."""
    out = np.array([e - s for _, s, e, _ in ops], np.float64)
    stack: List[int] = []
    for i, (_, s, e, _) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1]] -= e - s
        stack.append(i)
    return np.maximum(out, 0.0)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, window: Optional[Tuple[float, float]] = None) -> Trace:
    """Reduce the trace at ``path``.  ``window`` (ns) defaults to the span
    ``bench.traced``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans: List[Span] = []
    enqueued: Dict[int, float] = {}
    device_lines = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") or plane.name.startswith(
                "/device:CPU:"):
            device_lines.append({ln.name: ln for ln in plane.lines})
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith("bench."):
                        spans.append(Span(ev.name, ev.start_ns,
                                          ev.start_ns + ev.duration_ns,
                                          dict(ev.stats)))
                    elif ev.name == "DoEnqueueProgram":
                        st = dict(ev.stats)
                        if "run_id" in st:
                            enqueued.setdefault(int(st["run_id"]), ev.start_ns)
    if window is None:
        marks = [sp for sp in spans if sp.name == "bench.traced"]
        if not marks:
            raise ValueError("trace holds no bench.traced span")
        window = (marks[0].start, marks[0].end)
    t0, t1 = window
    calls = sorted((sp for sp in spans if sp.name[6:] in CALL_KINDS),
                   key=lambda sp: sp.start)

    def call_at(t):
        # The runtime enqueues a program a few tens of microseconds after
        # the Python call that launched it may have returned.
        for sp in calls:
            if sp.start <= t < sp.end + ENQUEUE_SLACK_NS:
                return sp
        return None

    modules: List[Module] = []
    ops: List[Tuple[str, float, float, int]] = []
    self_parts = []
    for lines in device_lines:
        first = len(modules)
        for ev in (lines["XLA Modules"].events if "XLA Modules" in lines
                   else []):
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e <= t0 or s >= t1:
                continue
            rid = dict(ev.stats).get("run_id")
            rid = int(rid) if rid is not None else None
            sp = call_at(enqueued.get(rid, s))
            modules.append(Module(
                ev.name, s, e, rid,
                kind=sp.name[6:] if sp else "other",
                call=int(sp.stats["call"]) if sp and "call" in sp.stats
                else None))
        mine = sorted(range(first, len(modules)),
                      key=lambda i: modules[i].start)
        starts = [modules[i].start for i in mine]
        dev_ops = []
        for ev in (lines["XLA Ops"].events if "XLA Ops" in lines else []):
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e <= t0 or s >= t1:
                continue
            j = bisect.bisect_right(starts, s) - 1
            m = mine[j] if j >= 0 and s < modules[mine[j]].end else -1
            dev_ops.append((ev.name, s, e, m))
        dev_ops.sort(key=lambda o: (o[1], -o[2]))
        ops += dev_ops
        self_parts.append(_self_times(dev_ops))
    self_ns = (np.concatenate(self_parts) if self_parts
               else np.zeros((0,), np.float64))
    return Trace(t0=t0, t1=t1, devices=max(len(device_lines), 1), ops=ops,
                 self_ns=self_ns, modules=modules, spans=spans)
