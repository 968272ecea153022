"""Benchmark harness entry point — one section per paper table/figure.

  fig4          paper Fig. 4 (tdFIR / MRI-Q automatic-offload speedups)
  conditions    paper §5.1.2 evaluation-conditions table (loop narrowing)
  extraction    static extractor precision/recall vs annotated archs +
                discover()-driven auto-planning of unannotated programs
  strategies    staged vs genetic vs exhaustive Step-4 search at equal budget
  autotune      tile-parameter autotuning: tuned vs fixed genome at equal d
  verification  serial vs pipelined pattern verification (core/executor.py)
  replanning    online replanning: hot-swap pause, pre/post-swap throughput,
                warm re-open measurement budget (serving/replan.py)
  faults        fault tolerance: retry/quarantine cost under an injected
                fault storm + mid-serve rollback tick pause (core/faults.py)
  kernels       kernel ref-vs-offload micro-bench + v5e roofline projection
  roofline      per-(arch x shape x mesh) roofline from the dry-run JSONL

With ``--json`` the conditions and strategies sections also write
``BENCH_<section>.json`` documents (CI uploads them as artifacts to track
the perf trajectory across commits).

Run:  PYTHONPATH=src python -m benchmarks.run [--section NAME] [--json]
"""
from __future__ import annotations

import argparse
import os
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--section", default="all",
                    choices=["all", "fig4", "conditions", "extraction",
                             "strategies", "autotune", "verification",
                             "replanning", "faults", "kernels", "roofline"])
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_<section>.json next to the cwd for the "
                         "sections that support it")
    ap.add_argument("--budget", type=int, default=4,
                    help="strategies section: measurement budget d")
    ap.add_argument("--reps", type=int, default=3,
                    help="strategies section: timing reps per pattern")
    ap.add_argument("--dryrun-jsonl", default=None)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.section in ("all", "conditions"):
        print("== paper §5.1.2 conditions (loop extraction & narrowing) ==")
        from benchmarks import loop_extraction
        loop_extraction.main(
            json_path="BENCH_conditions.json" if args.json else None)
        print()
    if args.section in ("all", "extraction"):
        print("== static extraction (recognizer accuracy + unannotated "
              "auto-plan) ==")
        from benchmarks import loop_extraction
        loop_extraction.main_extraction(
            json_path="BENCH_extraction.json" if args.json else None)
        print()
    if args.section in ("all", "strategies"):
        print("== search strategies (staged vs genetic vs exhaustive) ==")
        from benchmarks import strategies
        strategies.main(
            budget=args.budget, reps=args.reps,
            json_path="BENCH_strategies.json" if args.json else None)
        print()
    if args.section in ("all", "autotune"):
        print("== kernel autotuning (tuned vs fixed tile genome) ==")
        from benchmarks import autotune
        autotune.main(
            budget=max(args.budget, 8), reps=min(args.reps, 2),
            json_path="BENCH_autotune.json" if args.json else None)
        print()
    if args.section in ("all", "verification"):
        print("== pipelined pattern verification (serial vs concurrent AOT) ==")
        from benchmarks import verification
        verification.main(
            budget=max(args.budget, 8), reps=args.reps,
            json_path="BENCH_verification.json" if args.json else None)
        print()
    if args.section in ("all", "replanning"):
        print("== online replanning (hot-swap pause + warm re-open) ==")
        from benchmarks import replanning
        replanning.main(
            json_path="BENCH_replanning.json" if args.json else None)
        print()
    if args.section in ("all", "faults"):
        print("== fault tolerance (fault-storm retries + rollback pause) ==")
        from benchmarks import faults
        faults.main(
            json_path="BENCH_faults.json" if args.json else None)
        print()
    if args.section in ("all", "fig4"):
        print("== paper Fig. 4 (automatic offload speedup) ==")
        from benchmarks import fig4_offload
        fig4_offload.main()
        print()
    if args.section in ("all", "kernels"):
        print("== kernel bench (name,us_per_call,derived) ==")
        from benchmarks import kernel_bench
        kernel_bench.main()
        print()
    if args.section in ("all", "roofline"):
        from benchmarks import roofline, scaling
        path = args.dryrun_jsonl
        if path is None:
            for cand in ("results/dryrun_final.jsonl", "results/dryrun_v3.jsonl",
                         "results/dryrun_v2.jsonl", "results/dryrun.jsonl"):
                if os.path.exists(cand):
                    path = cand
                    break
        if path and os.path.exists(path):
            print(f"== roofline (single-pod, from {path}) ==")
            rows = roofline.load_rows(path)
            print(roofline.format_table(rows, "single"))
            print()
            print(f"== roofline (multi-pod, from {path}) ==")
            print(roofline.format_table(rows, "multi"))
            print()
            print("== weak scaling (1-pod vs 2-pod, dominant-term speedup) ==")
            sys.argv = ["scaling", "--in", path]
            scaling.main()
        else:
            print("== roofline: no dry-run JSONL found; run "
                  "`python -m repro.launch.dryrun --all` first ==")


if __name__ == "__main__":
    main()
