"""``python -m repro.scenarios`` — list, run, sweep, report.

    python -m repro.scenarios --list
    python -m repro.scenarios --run gemini-5hospital
    python -m repro.scenarios --sweep capacity-mini
    python -m repro.scenarios --sweep smoke-2x2 --assert-cached
    python -m repro.scenarios --report capacity-mini

``--sweep`` executes through the content-addressed cache (``--cache-dir``),
so a re-run only executes new/changed cells; ``--assert-cached`` turns a
fully-cached expectation into an exit code for CI.  ``--report`` re-renders
artifacts from cache alone, without executing anything.
"""

from __future__ import annotations

import argparse
import os
import sys

import repro.obs as obs
from repro.compile_cache import enable_compilation_cache
from repro.scenarios import grid as grid_lib
from repro.scenarios import presets as presets_lib
from repro.scenarios import report as report_lib
from repro.scenarios.cache import DEFAULT_CACHE_DIR, ResultCache
from repro.scenarios.executor import run_sweep


def _default_jobs() -> int:
    return min(4, os.cpu_count() or 1)


def _print_list() -> None:
    print("presets:")
    for name, spec in sorted(presets_lib.all_presets().items()):
        print(f"  {name:<24} task={spec.task:<9} H={spec.hospitals:<3} "
              f"size={spec.model_size:<7} tags={','.join(spec.tags)}")
    print("\nsweeps:")
    for name in sorted(grid_lib.SWEEPS):
        g = grid_lib.get_sweep(name)
        axes = ", ".join(f"{k}x{len(v)}" for k, v in sorted(g.axes.items()))
        print(f"  {name:<24} {g.size():>4} cells  ({axes})")


def _emit_artifacts(out_path: str, sweep_name: str, cells) -> None:
    out_json, out_md = report_lib.write_artifacts(sweep_name, cells, out_path)
    print(report_lib.markdown_report(sweep_name, cells))
    print(f"wrote {out_json} and {out_md}", file=sys.stderr)


def _sweep_cells(args, specs, sweep_name: str, default_out: str) -> int:
    cache = ResultCache(args.cache_dir)
    outcome = run_sweep(
        specs, cache, jobs=args.jobs, force=args.force,
        progress=lambda msg: print(msg, file=sys.stderr),
    )
    print(f"sweep {sweep_name}: {outcome.cells} cells "
          f"({outcome.hits} cached, {outcome.misses} ran) "
          f"in {outcome.elapsed:.1f}s", file=sys.stderr)
    _emit_artifacts(args.out or default_out, sweep_name, outcome.results)
    if args.assert_cached and outcome.misses:
        print(f"--assert-cached: {outcome.misses} cells were NOT served "
              "from cache", file=sys.stderr)
        return 1
    return 0


def _export_obs(args) -> None:
    if args.obs and obs.recorder() is not None:
        paths = obs.export(args.obs)
        obs.disable()
        print(f"obs: wrote {', '.join(str(v) for v in paths.values())}",
              file=sys.stderr)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m repro.scenarios",
        description="Declarative scenario suite + cached parallel sweeps.",
    )
    act = p.add_mutually_exclusive_group(required=True)
    act.add_argument("--list", action="store_true",
                     help="list presets and named sweeps")
    act.add_argument("--run", metavar="PRESET",
                     help="run one named preset (through the cache)")
    act.add_argument("--sweep", metavar="SWEEP",
                     help="run a named sweep (only cache misses execute)")
    act.add_argument("--report", metavar="SWEEP",
                     help="re-render a sweep's artifacts from cache only")
    p.add_argument("--cache-dir", default=DEFAULT_CACHE_DIR,
                   help=f"result cache directory (default {DEFAULT_CACHE_DIR})")
    p.add_argument("--jobs", type=int, default=_default_jobs(),
                   help="process-pool width for cache misses (1 = inline)")
    p.add_argument("--out", default=None,
                   help="artifact path, markdown lands beside it (default: "
                        "BENCH_sweep.json for --sweep/--report, "
                        "BENCH_run.json for --run — so one-off runs never "
                        "clobber the committed sweep trajectory)")
    p.add_argument("--force", action="store_true",
                   help="ignore cached results and re-run every cell")
    p.add_argument("--assert-cached", action="store_true",
                   help="exit 1 if any cell had to execute (CI cache check)")
    p.add_argument("--arm", help="override the arm for --run")
    p.add_argument("--obs", default=None, metavar="DIR",
                   help="record obs spans (per-cell phase breakdowns in the "
                        "BENCH rows) and export artifacts into DIR; "
                        "inline cells only — pool workers do not record")
    args = p.parse_args(argv)
    enable_compilation_cache()
    if args.obs:
        obs.enable()

    if args.list:
        _print_list()
        return 0

    if args.run:
        spec = presets_lib.get_preset(args.run)
        if args.arm:
            spec = spec.replace(arm=args.arm,
                                name=f"{spec.name}/arm={args.arm}")
        rc = _sweep_cells(args, [spec], spec.name, "BENCH_run.json")
        _export_obs(args)
        return rc

    if args.sweep:
        specs = grid_lib.get_sweep(args.sweep).specs()
        rc = _sweep_cells(args, specs, args.sweep, "BENCH_sweep.json")
        _export_obs(args)
        return rc

    # --report: cache-only re-render
    sweep = grid_lib.get_sweep(args.report)
    cache = ResultCache(args.cache_dir)
    cells, missing = [], []
    for spec in sweep.specs():
        cached = cache.get(spec)
        (cells.append(cached) if cached is not None
         else missing.append(spec.name))
    if missing:
        print(f"{len(missing)} of {sweep.size()} cells are not cached "
              f"(first: {missing[0]}); run --sweep {args.report} first",
              file=sys.stderr)
        return 1
    _emit_artifacts(args.out or "BENCH_sweep.json", args.report, cells)
    return 0
