"""Host milliseconds per ``Scheduler.step`` outside the family's calls.

The span ``bench.step`` around each step, less the spans of the device-
launching family calls inside it (``bench.prefill_batch``,
``bench.decode_steps``, ``bench.verify_steps``), averaged over the steps
that lie in the traced window.  The calls' spans include their own waits for
the device, so what is left is the scheduler's Python: admission, row
building, stream accounting, token hand-off, retirement.
"""
from bench.readers import host_ms_per_step


def read(run):
    return host_ms_per_step(run)
