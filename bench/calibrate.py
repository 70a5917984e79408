"""Readings that set the limits of ``correct``, for one cell, on the chip.

    python bench/calibrate.py --workload <name> --seeds 1,2,... \
        [--control 3] [--faults 3] [--out FILE]

For each seed: the program's first rounds, run as the window runs them,
against the reference (the sound readings, whose largest is a limit's lower
reading).  On the first ``--control`` seeds also the control, the
reference in bfloat16 in the program's place, and on the first ``--faults``
seeds the program with each fault of ``bench/faults.py`` planted (the upper
readings).  One JSON line per reading goes to ``--out`` and to stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the TPU runtime's own log files would land in a fixed /tmp directory
os.environ.setdefault("TPU_LOG_DIR", "disabled")
# the cache that bench/run.py uses, inside this checkout
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


def readings(cell, seeds, n_control: int, n_faults: int, emit) -> None:
    from bench import cohort, faults, harness

    clock = harness.CompileClock()
    for n, seed in enumerate(seeds):
        t0 = time.perf_counter()
        raw = cohort.cohort(cell.config, cell.traffic, seed)
        runs = [("program", None)]
        if n < n_faults:
            runs += list(faults.FAULTS.items())
        for kind, fault in runs:
            t = time.perf_counter()
            if fault is None:
                report, window = harness.drive(cell, raw, seed, 0.0, clock)
            else:
                with fault():
                    report, window = harness.drive(cell, raw, seed, 0.0,
                                                   clock)
            prog = harness.program_outputs(report, window)
            del report, window
            values = harness.check(cell, raw, seed, prog)
            emit({"cell": cell.name, "seed": seed, "kind": kind,
                  "seconds": time.perf_counter() - t, **values})
        if n < n_control:
            t = time.perf_counter()
            prog = harness.control_outputs(cell, raw, seed)
            values = harness.check(cell, raw, seed, prog)
            emit({"cell": cell.name, "seed": seed, "kind": "control",
                  "seconds": time.perf_counter() - t, **values})
        print(f"seed {seed}: {time.perf_counter() - t0:.1f} s",
              file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import jax

    from bench import harness

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    harness.enable_cache()
    cell = harness.load_cell(args.workload)
    out = open(args.out, "a") if args.out else None

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    try:
        readings(cell, [int(s) for s in args.seeds.split(",")],
                 args.control, args.faults, emit)
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
