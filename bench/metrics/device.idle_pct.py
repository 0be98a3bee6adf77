"""Share of the traced window, in %, in which no op runs on the device.

Busy time is the union of the ``XLA Ops`` intervals; the time in which the
harness slept with no request queued or resident (span
``bench.idle_wait``) is left out of the window and of the idle time.
"""
from bench.readers import idle_pct


def read(run):
    return idle_pct(run)
