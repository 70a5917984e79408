"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--full`` runs paper-scale sizes
(hours); the default fast mode validates every claim at reduced scale.
"""

from __future__ import annotations

import argparse
import sys
import traceback

from repro.compile_cache import enable_compilation_cache

MODULES = [
    ("gemini_utility", "Fig 2c / Supp T4-5: GEMINI mortality (4 arms)"),
    ("pancreas_utility", "Fig 3c / Supp T6-7: pancreas cell typing (4 arms)"),
    ("xray_utility", "Fig 4c / Supp T8: chest radiology (4 arms)"),
    ("mia", "Fig 5: LiRA membership inference, FL vs DeCaPH"),
    ("secagg_cost", "Supp Fig 1 / Supp T1: SecAgg wall-clock + comm"),
    ("sim_report", "Systems: 5 arms on a heterogeneous trace + dropout recovery"),
    ("hotpath", "Systems: fused round-step vs loop (wall/round + dispatches)"),
    ("pate_ablation", "Supp (Existing frameworks): PATE vs DeCaPH ablation"),
    ("accountant_table", "Methods: RDP accounting for the paper's budgets"),
    ("kernel_bench", "Kernels: oracle timings + traffic ratios"),
    ("roofline_report", "Systems: roofline terms from dry-run artifacts"),
]


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--full", action="store_true", help="paper-scale sizes")
    p.add_argument("--only", default=None,
                   help="comma-separated module names to run")
    args = p.parse_args()
    enable_compilation_cache()

    only = set(args.only.split(",")) if args.only else None
    print("name,us_per_call,derived")
    failed = []
    for mod_name, desc in MODULES:
        if only and mod_name not in only:
            continue
        print(f"# {mod_name}: {desc}", file=sys.stderr)
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            rows = mod.run(fast=not args.full)
            for r in rows:
                print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
            sys.stdout.flush()
        except Exception as e:
            traceback.print_exc(limit=6, file=sys.stderr)
            print(f"{mod_name}_FAILED,0,{type(e).__name__}:{e}")
            failed.append(mod_name)
    if failed:
        print(f"# FAILED modules: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
