"""Benchmark orchestrator — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows per the harness contract
(documented in benchmarks/README.md), then structured sections for
Fig. 3a-e, Fig. 5a-c, and (when dry-run artifacts exist) the roofline
table.  Serving speed is measured on the chip by ``bench/run.py``.

    PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    from repro.runtime.device import use_compile_cache
    use_compile_cache()
    t0 = time.time()

    print("name,us_per_call,derived")

    # ---- kernel micro-benchmarks -------------------------------------
    from . import kernelbench
    for row in kernelbench.run():
        print(f"{row['name']},{row['us_per_call']:.1f},{row['derived']}")

    # ---- Fig 3a: speedups & bus utilizations -------------------------
    from .paper_workloads import (
        fig3a_rows, gemv_model, trmv_model, evaluate,
    )
    from repro.core import System

    n_sparse = 64 if args.quick else 192
    print("\n# Fig3a (model): workload, PACK speedup, bus util, PACK/IDEAL")
    paper = {"ismt": (5.4, 0.50), "gemv-col": (None, 0.87),
             "trmv-col": (None, 0.72), "spmv": (2.4, None),
             "prank": (None, None), "sssp": (None, 0.39)}
    for r in fig3a_rows(n=256, sparse_rows=n_sparse, avg_nnz=390):
        ps, pu = paper.get(r.name, (None, None))
        ref_s = f" (paper {ps}x)" if ps else ""
        ref_u = f" (paper {pu:.0%})" if pu else ""
        print(f"fig3a,{r.name},speedup={r.speedup_pack:.2f}x{ref_s},"
              f"util={r.util_pack:.1%}{ref_u},pack/ideal={r.pack_vs_ideal:.1%}")

    # ---- Fig 3b/c: dataflow comparisons -------------------------------
    from .fig3_scaling import (
        fig3b_gemv_dataflows, fig3c_trmv_dataflows,
        fig3d_ismt_scaling, fig3e_spmv_scaling,
    )
    print("\n# Fig3b/c: row vs col dataflow cycles")
    for name, table in (("gemv", fig3b_gemv_dataflows()),
                        ("trmv", fig3c_trmv_dataflows())):
        for flow, vals in table.items():
            print(f"fig3bc,{name}-{flow},base={vals['base']:.0f},"
                  f"pack={vals['pack']:.0f},ideal={vals['ideal']:.0f},"
                  f"util_pack={vals['util_pack']:.1%}")

    # ---- Fig 3d/e: scaling --------------------------------------------
    print("\n# Fig3d: ismt speedup vs size x width (paper peaks 1.9/3.2/5.4)")
    for row in fig3d_ismt_scaling(sizes=(8, 32, 128, 256) if args.quick else
                                  (8, 16, 32, 64, 128, 256)):
        print(f"fig3d,bus={row['bus_bits']},n={row['n']},speedup={row['speedup']:.2f}")
    print("\n# Fig3e: spmv speedup vs nnz/row x width (paper peaks 1.4/1.8/2.4)")
    for row in fig3e_spmv_scaling(n_rows=32 if args.quick else 96):
        print(f"fig3e,bus={row['bus_bits']},nnz={row['avg_nnz']},speedup={row['speedup']:.2f}")

    # ---- Fig 5: endpoint sensitivity ----------------------------------
    from .fig5_sensitivity import fig5a_indirect, fig5b_strided, fig5c_crossbar_area
    print("\n# Fig5a: indirect utilization vs (elem,index) x banks")
    pairs = ((32, 32), (32, 16), (32, 8)) if args.quick else None
    banks = (8, 16, 17, 32) if args.quick else None
    kw = {}
    if pairs:
        kw["pairs"] = pairs
    if banks:
        kw["bank_counts"] = banks
    for row in fig5a_indirect(**kw):
        print(f"fig5a,e{row['elem_bits']}i{row['index_bits']},banks={row['banks']},"
              f"util={row['utilization']:.3f},ceiling={row['ceiling_r_over_r1']:.3f}")
    print("\n# Fig5b: strided mean utilization (strides 0-63)")
    kw = {"bank_counts": banks} if banks else {}
    if args.quick:
        kw["strides"] = range(0, 16)
    for row in fig5b_strided(**kw):
        print(f"fig5b,e{row['elem_bits']},banks={row['banks']},"
              f"util={row['mean_utilization']:.3f},prime={row['prime']}")
    print("\n# Fig5c: crossbar area model")
    for row in fig5c_crossbar_area():
        print(f"fig5c,banks={row['banks']},kGE={row['area_kge']:.1f},prime={row['prime']}")

    # ---- Roofline (if dry-run artifacts exist) ------------------------
    try:
        from .roofline import roofline_rows, print_table
        rows = roofline_rows("pod16x16")
        if rows:
            print("\n# Roofline (single-pod dry-run artifacts)")
            print_table(rows)
    except Exception as e:  # noqa: BLE001
        print(f"\n# Roofline skipped: {e}")

    print(f"\n# total {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
