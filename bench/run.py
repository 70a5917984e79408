"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` measures the cell's end-to-end metrics; ``--trace 1`` records
the program's spans and a profiler trace of the window and reports the
per-layer metrics, the device's busy time and a breakdown.  Every run checks
the first rounds against the reference (``correct``).  The last line of
stdout is one JSON object.  Without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the TPU runtime's own log files would land in a fixed /tmp directory
os.environ.setdefault("TPU_LOG_DIR", "disabled")
# JAX's persistent cache at a fixed path inside this checkout, whatever the
# machine sets: JAX reads the variable at import, the program then keeps it
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")

    from bench import harness

    cell = harness.load_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    print(f"compile cache: {harness.enable_cache()}", flush=True)
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
