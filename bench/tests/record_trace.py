#!/usr/bin/env python3
"""Run one cell as ``bench/run.py`` does, and keep its profile.

    python bench/tests/record_trace.py <dir> <traced seconds> <run.py arguments>

The run is ``bench/run.py``'s own ``main`` with two differences: the first
``<traced seconds>`` of the window are profiled (``TRACE_SECONDS``), and the
profile (``plugins/profile/<time>/<host>.xplane.pb``) and the harness's
``calls.json`` stay in ``<dir>`` instead of a temporary directory.  Give it
``--trace 1``.  ``data/longctx-trace-spans/`` was recorded this way on one
TPU v5e, with a half-second window::

    python bench/tests/record_trace.py chiprun_out/spans 0.5 \\
        --workload yi6b-attn-l4.longctx --seed <n> --seconds 3 --trace 1

The tests run it as a CPU rehearsal (``--rehearse``).
"""
import os
import sys
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class _Kept:
    """A ``TemporaryDirectory`` stand-in that keeps its directory."""

    def __init__(self, name):
        self.name = name

    def cleanup(self):
        pass


def main(argv) -> int:
    out, seconds, rest = os.path.abspath(argv[0]), float(argv[1]), argv[2:]
    os.makedirs(out, exist_ok=True)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import run

    run.TRACE_SECONDS = seconds
    run.tempfile = SimpleNamespace(
        TemporaryDirectory=lambda **_: _Kept(out))
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
