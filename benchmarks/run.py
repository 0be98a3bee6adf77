"""Benchmark orchestrator — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows per the harness contract
(documented in benchmarks/README.md), then structured sections for
Fig. 3a-e, Fig. 5a-c, the continuous-batching serving sweep, and (when
dry-run artifacts exist) the roofline table.

``--json PATH`` additionally writes the serving sweep as machine-readable
JSON (tokens/s, steps/s, PACK/BASE efficiency per batch size) so the perf
trajectory can be tracked run-over-run (CI uploads it as an artifact).

    PYTHONPATH=src python -m benchmarks.run [--quick] [--json BENCH_serving.json]
"""
from __future__ import annotations

import argparse
import datetime
import json
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the serving sweep as JSON to PATH")
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

    # ---- Serving: continuous batching over paged streams --------------
    from .serving import serving_rows
    print("\n# Serving: decode tokens/s vs batch; per-step PACK vs BASE bytes")
    srows = serving_rows(quick=args.quick)
    for row in srows:
        print(f"serving,b={row['batch']},tokens_s={row['tokens_per_s']:.0f},"
              f"steps_s={row['steps_per_s']:.0f},"
              f"decode_steps={row['decode_steps']},"
              f"evictions={row['evictions']},"
              f"pack_KiB={row['pack_kib']:.0f},base_KiB={row['base_kib']:.0f},"
              f"pack_eff={row['pack_eff']:.1%},base_eff={row['base_eff']:.1%}")
    print("\n# Serving prefill: batched chunked-prefill tokens/s; "
          "PACK vs BASE efficiency of the prefill streams")
    for row in srows:
        print(f"serving_prefill,b={row['batch']},"
              f"prompt_tokens={row['prompt_tokens']},"
              f"prefill_tokens_s={row['prefill_tokens_per_s']:.0f},"
              f"prefill_steps={row['prefill_steps']},"
              f"pack_eff={row['prefill_pack_eff']:.1%},"
              f"base_eff={row['prefill_base_eff']:.1%}")

    # ---- Serving, int8 page pools: the element-size lever ---------------
    # Same prompts / workload as the fp32 sweep, but the pools hold int8
    # codes + fp32 scale sidebands: quantize-on-write, in-kernel dequant,
    # and the 8-bit packing factor in the PACK accounting.
    print("\n# Serving int8: quantized page pools (pool bytes ÷4 vs fp32; "
          "PACK packs 4x more elements per granule)")
    irows = serving_rows(quick=args.quick, kv_dtype="int8")
    fp_by_batch = {r["batch"]: r for r in srows}
    for row in irows:
        fp = fp_by_batch[row["batch"]]
        print(f"serving_int8,b={row['batch']},"
              f"tokens_s={row['tokens_per_s']:.0f},"
              f"vs_fp32={row['tokens_per_s'] / fp['tokens_per_s']:.2f}x,"
              f"pack_KiB={row['pack_kib']:.0f},"
              f"pool_bytes={row['pool_bytes']},"
              f"pool_vs_fp32={fp['pool_bytes'] / row['pool_bytes']:.2f}x,"
              f"pack_eff={row['pack_eff']:.1%},base_eff={row['base_eff']:.1%},"
              f"prefill_pack_eff={row['prefill_pack_eff']:.1%}")

    # ---- Serving, prefix sharing: refcounted pages + CoW ----------------
    # Batches repeating one page-aligned system prompt: shared pages admit
    # by refcount bump (PACK moves only remapped table indices), the
    # divergent tails prefill normally, and the row asserts bit-for-bit
    # output equality against a non-sharing scheduler.
    from .serving import shared_prefix_rows
    print("\n# Serving shared-prefix: prefill tokens saved via refcounted "
          "page sharing (outputs bit-for-bit vs non-sharing)")
    prows = shared_prefix_rows(quick=args.quick)
    for row in prows:
        print(f"serving_shared_prefix,b={row['batch']},"
              f"prompt_tokens={row['prompt_tokens']},"
              f"saved={row['prefill_tokens_saved']},"
              f"saved_frac={row['saved_frac']:.1%},"
              f"shared_pages={row['shared_pages']},"
              f"cow_copies={row['cow_copies']},"
              f"pack_eff={row['prefill_pack_eff']:.1%},"
              f"effective_pack_eff={row['effective_pack_eff']:.1%},"
              f"plain_pack_eff={row['plain_pack_eff']:.1%},"
              f"outputs_match={row['outputs_match']}")

    # ---- Serving, model families: recurrent state through the same
    # scheduler.  RWKV6/Mamba serve out of fixed-size state slots via the
    # ServableFamily protocol; the accounting dialect flips from indirect
    # page walks to strided state bursts (no index-bus term), and every row
    # asserts bit-for-bit equality with the direct sequential forward.
    from .serving import family_rows
    print("\n# Serving families: recurrent models (strided state bursts) "
          "through the shared scheduler (outputs bit-for-bit vs direct "
          "forward)")
    frows = family_rows(quick=args.quick)
    for row in frows:
        print(f"serving_families,{row['family']},b={row['batch']},"
              f"tokens_s={row['tokens_per_s']:.0f},"
              f"decode_steps={row['decode_steps']},"
              f"pack_KiB={row['pack_kib']:.0f},base_KiB={row['base_kib']:.0f},"
              f"pack_eff={row['pack_eff']:.1%},base_eff={row['base_eff']:.1%},"
              f"state_slot_bytes={row['state_slot_bytes']},"
              f"outputs_match={row['outputs_match']}")

    # ---- Serving, speculative decoding: draft-k, verify once ------------
    # The n-gram drafter proposes spec_k-1 tokens per step; one
    # paged_verify launch scores all of them in a single clamped page walk
    # and the accept/reject + KV rollback run on device.  Each row re-runs
    # the spec_k=1 workload and asserts the emitted outputs are bit-for-bit
    # the plain greedy outputs.
    from .serving import spec_rows
    print("\n# Serving speculative: multi-query verify tokens/s vs plain "
          "fused decode (outputs bit-for-bit vs spec_k=1)")
    specrows = spec_rows(quick=args.quick)
    for row in specrows:
        print(f"serving_spec,b={row['batch']},k={row['spec_k']},"
              f"tokens_s={row['tokens_per_s']:.0f},"
              f"speedup={row['speedup_vs_plain']:.2f}x,"
              f"acceptance={row['acceptance_rate']:.1%},"
              f"verify_steps={row['verify_steps']},"
              f"plain_decode_steps={row['plain_decode_steps']},"
              f"pack_eff={row['pack_eff']:.1%},base_eff={row['base_eff']:.1%},"
              f"outputs_match={row['outputs_match']}")

    # ---- Serving, degradation: throughput under pool pressure + chaos ---
    # Mixed-SLA workload vs shrinking pools and a seeded fault plan: the
    # robustness counters (evictions / preemptions / rejections / deadline
    # misses) next to tokens/s, with liveness (all_terminal) and replay
    # correctness (outputs_match) asserted per row.
    from .serving import degradation_rows
    print("\n# Serving degradation: tokens/s + SLA counters under pool "
          "pressure and injected faults")
    drows = degradation_rows(quick=args.quick)
    for row in drows:
        print(f"serving_degradation,{row['label']},"
              f"pool_pages={row['pool_pages']},"
              f"tokens_s={row['tokens_per_s']:.0f},"
              f"completed={row['completed']},"
              f"evictions={row['evictions']},"
              f"preemptions={row['preemptions']},"
              f"rejections={row['rejections']},"
              f"deadline_misses={row['deadline_misses']},"
              f"all_terminal={row['all_terminal']},"
              f"outputs_match={row['outputs_match']}")

    if args.json:
        def _json_row(r):
            return {
                "batch": r["batch"],
                "tokens": r["tokens"],
                "wall_s": r["wall_s"],
                "tokens_per_s": r["tokens_per_s"],
                "steps_per_s": r["steps_per_s"],
                "decode_steps": r["decode_steps"],
                "evictions": r["evictions"],
                "pack_efficiency": r["pack_eff"],
                "base_efficiency": r["base_eff"],
                "prompt_tokens": r["prompt_tokens"],
                "prefill_steps": r["prefill_steps"],
                "prefill_tokens_per_s": r["prefill_tokens_per_s"],
                "prefill_pack_efficiency": r["prefill_pack_eff"],
                "prefill_base_efficiency": r["prefill_base_eff"],
                "kv_elem_bits": r["kv_elem_bits"],
                "pool_bytes": r["pool_bytes"],
            }

        payload = {
            "benchmark": "serving",
            "quick": bool(args.quick),
            "rows": [_json_row(r) for r in srows],
            "serving_int8": {
                "rows": [dict(
                    _json_row(r),
                    tokens_per_s_vs_fp32=(
                        r["tokens_per_s"]
                        / fp_by_batch[r["batch"]]["tokens_per_s"]
                    ),
                    pool_bytes_vs_fp32=(
                        fp_by_batch[r["batch"]]["pool_bytes"]
                        / r["pool_bytes"]
                    ),
                ) for r in irows],
            },
            "serving_shared_prefix": {"rows": prows},
            "serving_families": {"rows": frows},
            "serving_spec": {"rows": specrows},
            "serving_degradation": {"rows": drows},
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# serving sweep written to {args.json}")

        # One dated line per run so the perf trajectory is greppable
        # without diffing full artifacts.  Lives next to the JSON path;
        # the committed full-sweep history is BENCH_history.jsonl at the
        # repo root, quick CI runs append to their own workspace copy.
        hist = os.path.join(
            os.path.dirname(os.path.abspath(args.json)) or ".",
            "BENCH_history.jsonl")
        spec_best = max(specrows, key=lambda r: r["speedup_vs_plain"])
        entry = {
            "date": datetime.date.today().isoformat(),
            "quick": bool(args.quick),
            "decode_tokens_per_s": {
                str(r["batch"]): round(r["tokens_per_s"], 1) for r in srows},
            "spec_best": {
                "batch": spec_best["batch"],
                "spec_k": spec_best["spec_k"],
                "speedup_vs_plain": round(spec_best["speedup_vs_plain"], 3),
                "acceptance_rate": round(spec_best["acceptance_rate"], 3),
            },
            "spec_outputs_match": all(r["outputs_match"] for r in specrows),
        }
        with open(hist, "a") as f:
            f.write(json.dumps(entry) + "\n")
        print(f"# history entry appended to {hist}")

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
