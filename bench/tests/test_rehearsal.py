"""Every cell runs end to end on the CPU at its rehearsal sizes; a new
configuration, traffic mix and metric are files plus entries, nothing more."""
import json
import os
import shutil

import pytest

from conftest import ROOT, run_cell

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal(cell):
    rc, result, err = run_cell(cell, 3_000_000_011)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True
    assert result["device"]["platform"] == "cpu"
    assert list(result)[-1] == "checks"
    assert result["attempted"] > 0
    assert "setup_s" in result["metrics"]


def test_refuses_without_a_chip():
    import subprocess
    import sys
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    rc, result, _ = run_cell(CELLS[0], 1, root=str(tmp_path))
    assert rc != 0 and result is None


def test_new_cell_is_files_and_entries(tmp_path):
    """A configuration, an open-loop mix and a metric added the way a later
    PR adds them: new files and new entries, no existing file edited."""
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    cfg = json.load(open(os.path.join(
        ROOT, "bench", "configs", "yi6b-attn-l4.json")))
    cfg["name"] = "yi6b-attn-l2"
    cfg["rehearsal"]["num_hidden_layers"] = 1
    json.dump(cfg, open(tmp_path / "bench" / "configs" / "yi6b-attn-l2.json",
                        "w"))
    mix = {"loop": "open", "arrivals": "gamma", "cv": 2.0, "rate": 3.0,
           "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                      "lo": 8, "hi": 48},
           "output": {"dist": "uniform", "lo": 2, "hi": 6},
           "max_total": 52, "pool": 64, "sizes_seed": 1, "ramp_s": 1.0}
    json.dump(mix, open(tmp_path / "bench" / "traffic" / "chat-open.json", "w"))
    (tmp_path / "bench" / "metrics" / "decode.calls.py").write_text(
        "def read(run):\n"
        "    return sum(1 for c in run.calls if c['kind'] == 'decode_steps')\n")
    cell = "yi6b-attn-l2.chat-open"
    bench["configs"].append(dict(bench["configs"][0], name="yi6b-attn-l2",
                                 file="bench/configs/yi6b-attn-l2.json"))
    bench["workloads"].append({"name": cell, "config": "yi6b-attn-l2",
                               "traffic": "chat-open", "chips": 1,
                               "why": "test"})
    for name in ("ttft_p95_ms", "tpot_p95_ms"):
        bench["end_to_end"].append({"name": name, "unit": "ms",
                                    "better": "lower", "bound": 0.1,
                                    "source": "host_clock",
                                    "workloads": [cell]})
    bench["per_layer"].append({"name": "decode.calls", "unit": "count",
                               "better": "lower", "source": "program_span",
                               "layer": "Engines", "moves": "tpot_p95_ms",
                               "workloads": [cell]})
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))
    rc, result, err = run_cell(cell, 5, root=str(tmp_path), seconds=4)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True
    assert set(result["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    assert result["attempted"] > 0
    rc, result, err = run_cell(cell, 6, "--trace", "1", root=str(tmp_path),
                               seconds=4)
    assert rc == 0, err[-3000:]
    assert set(result["metrics"]) == {"decode.calls"}
    assert result["metrics"]["decode.calls"]["value"] > 0
