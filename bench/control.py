#!/usr/bin/env python3
"""Run a cell with the control in the program's place, once per seed.

    python bench/control.py --workload <cell> --seeds <a,b,...> --seconds <s>

Each run is a run of ``bench/run.py`` in one process: the program serves
the cell's traffic for the window, and then, at every position of the
sampled prompts and served tokens, the control (the plain reference at the
precision below the configuration's) chooses the token that is compared.
Each run prints the program's own ``served_gap`` on an earlier line and
its result line, whose ``correct`` has to come out false.  The benchmark's
own runs never run the control; this is how the limit's upper reading is
taken.  ``--rehearse`` runs it on the CPU at the rehearsal sizes.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run as harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    rc = 0
    for seed in args.seeds.split(","):
        print(f"control seed {seed}", flush=True)
        rc = rc or harness.main(
            ["--workload", args.workload, "--seed", seed, "--seconds",
             str(args.seconds)] + (["--rehearse"] if args.rehearse else []),
            control=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
