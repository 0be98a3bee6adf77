"""Host spans of the serving path, on the profiler's clock.

Each span is a :class:`jax.profiler.TraceAnnotation`.  While a profiler
session runs (``jax.profiler.start_trace``) it is an event of the host plane
of the trace, with its keyword stats, on the same clock as the device's
``XLA Ops``, so an idle stretch of the device can be put down to what the
host was doing in it.  With no session it costs about a microsecond, so the
spans are always in the code.

Where each span is opened:

* ``sched.step`` (``step``): one :meth:`repro.serve.scheduler.Scheduler.step`;
  inside it ``sched.admit``, ``sched.prefill``, ``sched.decode`` (with
  ``sched.grow``) and ``sched.retire``, one per phase;
* ``sched.handoff``: appending served tokens to their requests and calling
  ``on_token``;
* ``engine.launch`` (``kind``, ``n`` or ``rows``, ``ctx_pages``; for paged
  decode ``ppb`` and ``block_fill``; for recurrent launches ``row_fill``):
  one jitted program call, from the call to its return (dispatch and
  argument transfer);
* ``engine.reset`` (``slots``): the family's reset of an admitted slot
  (``replay``), opened by the scheduler's admission (host work only: a
  recurrent slot's state is zeroed by its first prefill launch);
* ``engine.sync`` (``kind``): a host read that waits for a program's result;
* ``gc`` (``generation``, ``collected``): a collection of generation 1 or 2
  (:func:`install_gc_spans`).

``docs/serving.md`` says what each stat is for.
"""
from __future__ import annotations

import gc
from typing import Optional

import jax

__all__ = ["SPANS", "install_gc_spans", "span"]

#: Every span name the program writes.
SPANS = (
    "sched.step",
    "sched.admit",
    "sched.prefill",
    "sched.decode",
    "sched.grow",
    "sched.handoff",
    "sched.retire",
    "engine.launch",
    "engine.sync",
    "engine.reset",
    "gc",
)


def span(name: str, **stats) -> jax.profiler.TraceAnnotation:
    """A context manager for the host span ``name`` with ``stats`` (ints or
    strings); stats known only at the end go in through ``set_metadata``."""
    return jax.profiler.TraceAnnotation(name, **stats)


class _GcSpans:
    """``gc.callbacks`` hook: a ``gc`` span from each start to its stop.

    Generation 0 is left out: it runs hundreds of times a second and its
    pauses are microseconds.  Collections never nest, so one open span is
    all the state there is.
    """

    def __init__(self):
        self.open: Optional[jax.profiler.TraceAnnotation] = None

    def __call__(self, phase: str, info: dict) -> None:
        if info["generation"] == 0:
            return
        if phase == "start":
            self.open = span("gc", generation=info["generation"])
            self.open.__enter__()
        elif self.open is not None:
            self.open.set_metadata(collected=info["collected"])
            self.open.__exit__(None, None, None)
            self.open = None


_GC_SPANS = _GcSpans()


def install_gc_spans() -> None:
    """Open a ``gc`` span around every collection of generation 1 or 2 in
    this process, with ``generation`` and ``collected`` as stats.
    Idempotent."""
    if _GC_SPANS not in gc.callbacks:
        gc.callbacks.append(_GC_SPANS)
