"""Shared helpers for the benchmark's own tests (``pytest bench/tests``).

They run on the CPU: the harness's rehearsal sizes, Pallas in interpret
mode.  None of them measures anything.
"""
import json
import os
import subprocess
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def run_cell(workload, seed, *extra, root=ROOT, seconds=2, timeout=900):
    """Run ``bench/run.py`` under ``root`` as a CPU rehearsal; returns the
    exit code, the parsed last line (or None) and the stderr text."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"),
         "--workload", workload, "--seed", str(seed),
         "--seconds", str(seconds), "--rehearse", *extra],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout)
    last = proc.stdout.strip().splitlines()[-1:] or [""]
    try:
        result = json.loads(last[0])
    except json.JSONDecodeError:
        result = None
    return proc.returncode, result, proc.stderr


@pytest.fixture(scope="session")
def root():
    return ROOT
