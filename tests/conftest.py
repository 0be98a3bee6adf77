import importlib.util
import os
import sys

# The suite runs on the CPU (Pallas kernels in interpret mode) and never
# takes a chip: a TPU belongs to one process at a time.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

# The property tests import `hypothesis`; it is an optional dev dependency.
# When absent, install the deterministic stub (tests/_hypothesis_stub.py) under
# the `hypothesis` name before collection so `pytest -x -q` runs everywhere.
if importlib.util.find_spec("hypothesis") is None:
    _spec = importlib.util.spec_from_file_location(
        "hypothesis",
        os.path.join(os.path.dirname(__file__), "_hypothesis_stub.py"),
    )
    _mod = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_mod)
    sys.modules["hypothesis"] = _mod
    sys.modules["hypothesis.strategies"] = _mod.strategies
