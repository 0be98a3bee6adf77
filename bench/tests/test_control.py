"""The control, put in the program's place, comes out not correct.

``bench/control.py`` drives a whole run of each cell (here the CPU
rehearsal, at the configuration's and the mix's rehearsal sizes) and then
hands ``compare`` the tokens that the control (the plain reference with
float8 e4m3 projection inputs, the precision below the program's bfloat16
products) puts first at each position of the same prompts and served
tokens.  The run's ``correct`` has to read false on every seed, while the
program's own gap in the same run stays under the limit.
"""
import contextlib
import io
import json
import os
import re

import pytest

from bench import control
from conftest import ROOT

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
SEEDS = ["3000000031", "3000000032", "2147483651"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_exceeds_limit(cell):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = control.main(["--workload", cell, "--seeds", ",".join(SEEDS),
                           "--seconds", "2", "--rehearse"])
    assert rc == 0
    text = out.getvalue()
    results = [json.loads(ln) for ln in text.splitlines()
               if ln.startswith("{")]
    assert len(results) == len(SEEDS)
    own = [float(v) for v in re.findall(r"^program served_gap (\S+)$", text,
                                        re.M)]
    assert len(own) == len(SEEDS)
    for result, gap in zip(results, own):
        check = result["checks"]["served_gap"]
        assert result["correct"] is False, check
        assert check["value"] > check["limit"]
        assert gap <= check["limit"]
