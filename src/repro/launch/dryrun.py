import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Test hook only: allow scaling the placeholder device count down BEFORE jax
# initializes (jax locks the device count on first init).
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ["REPRO_DRYRUN_DEVICES"]
    )

"""Multi-pod dry-run: prove every (arch x shape x mesh) lowers AND compiles.

For each combination this lowers the paper-faithful DeCaPH train step (or the
serve/prefill program for inference shapes) onto the production mesh with 512
placeholder CPU devices, compiles it, prints memory/cost analysis, and writes
a JSON artifact with the trip-count-corrected roofline terms
(launch/roofline.py) into ``benchmarks/artifacts/dryrun/``.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-360m --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--skip-existing]
"""

import argparse
import json
import time
import traceback

import jax

from repro.configs import ARCHITECTURES, INPUT_SHAPES, get_config
from repro.configs.shapes import ShapeSkip
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import analyze_compiled, model_flops, roofline_terms
from repro.launch.steps import build_program

ARTIFACT_DIR = os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "benchmarks", "artifacts", "dryrun"
)


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            mesh=None, dp_mode: str | None = None, policy=None,
            out_dir: str | None = None, tag: str = "",
            cfg_overrides: dict | None = None) -> dict:
    """Lower + compile one (arch, shape, mesh) and write the artifact."""
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = cfg.replace(**cfg_overrides)
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    t0 = time.time()
    program = build_program(cfg, shape_name, mesh, policy=policy, dp_mode=dp_mode)
    donate = (1,) if program.kind == "decode" else ()
    with jax.set_mesh(mesh):
        lowered = jax.jit(program.fn, donate_argnums=donate).lower(*program.args_sds)
        t_lower = time.time() - t0
        compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = {}
    try:
        ma = compiled.memory_analysis()
        print(f"[{arch} x {shape_name} x {mesh_name}] memory_analysis:", ma)
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes"):
            if hasattr(ma, attr):
                mem[attr] = int(getattr(ma, attr))
    except Exception as e:
        print("memory_analysis unavailable:", e)
    try:
        ca = compiled.cost_analysis()
        print(f"[{arch} x {shape_name} x {mesh_name}] cost_analysis flops:",
              ca.get("flops"), "bytes:", ca.get("bytes accessed"))
    except Exception as e:
        print("cost_analysis unavailable:", e)

    analysis = analyze_compiled(compiled)
    n_chips = int(mesh.devices.size)
    # The partitioned HLO carries PER-DEVICE shapes; scale to global so the
    # roofline formulas (which divide by chips x peak) apply consistently.
    # Replicated compute (e.g. attention that cannot shard over "model") is
    # genuinely duplicated across ranks and therefore genuinely counted.
    for k in ("corrected_flops", "collective_bytes", "toplevel_result_bytes",
              "hbm_traffic_model_bytes", "dot_bytes", "dus_bytes"):
        analysis[k] = analysis[k] * n_chips
    analysis["collective_by_kind"] = {
        k: v * n_chips for k, v in analysis["collective_by_kind"].items()
    }
    mf = model_flops(program.cfg, INPUT_SHAPES[shape_name], program.kind)
    hlo_flops = analysis["corrected_flops"]
    terms = roofline_terms(
        flops=hlo_flops,
        hbm_bytes=analysis["hbm_traffic_model_bytes"],
        coll_bytes=analysis["collective_bytes"],
        n_chips=n_chips,
    )
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "axis_names": list(mesh.axis_names),
        "n_chips": n_chips,
        "kind": program.kind,
        "meta": program.meta,
        "lower_s": t_lower,
        "compile_s": t_compile,
        "model_flops": mf,
        "useful_flops_ratio": mf / hlo_flops if hlo_flops else None,
        **analysis,
        "roofline": terms,
        "tag": tag,
    }
    out_dir = out_dir or ARTIFACT_DIR
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}{suffix}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=2, default=str)
    try:  # cache the optimized HLO so analyses can be re-run w/o recompiling
        import zstandard as zstd

        hlo_path = path.replace(".json", ".hlo.zst")
        with open(hlo_path, "wb") as f:
            f.write(zstd.ZstdCompressor(level=6).compress(
                compiled.as_text().encode()
            ))
    except Exception as e:  # pragma: no cover
        print("HLO cache write failed:", e)
    print(
        f"[{arch} x {shape_name} x {mesh_name}] OK "
        f"lower={t_lower:.1f}s compile={t_compile:.1f}s "
        f"flops={hlo_flops:.3e} coll={analysis['collective_bytes']:.3e}B "
        f"bottleneck={terms['bottleneck']}"
    )
    return record


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", choices=list(ARCHITECTURES), default=None)
    p.add_argument("--shape", choices=list(INPUT_SHAPES), default=None)
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--all", action="store_true",
                   help="every (arch x shape) on the selected mesh")
    p.add_argument("--skip-existing", action="store_true")
    p.add_argument("--dp-mode", default=None,
                   choices=["per_example", "ghost", "none"])
    p.add_argument("--tag", default="")
    p.add_argument("--out", default=None)
    args = p.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    combos = []
    if args.all:
        for arch in ARCHITECTURES:
            for shape in INPUT_SHAPES:
                combos.append((arch, shape))
    else:
        if not args.arch or not args.shape:
            p.error("--arch and --shape required unless --all")
        combos = [(args.arch, args.shape)]

    failures, skips = [], []
    for arch, shape in combos:
        out_dir = args.out or ARTIFACT_DIR
        suffix = f"__{args.tag}" if args.tag else ""
        path = os.path.join(out_dir, f"{arch}__{shape}__{mesh_name}{suffix}.json")
        if args.skip_existing and os.path.exists(path):
            print(f"[{arch} x {shape}] exists, skipping")
            continue
        try:
            run_one(arch, shape, mesh=mesh, dp_mode=args.dp_mode,
                    out_dir=args.out, tag=args.tag)
        except ShapeSkip as e:
            print(f"[{arch} x {shape}] SKIP: {e}")
            skips.append((arch, shape, str(e)))
        except Exception as e:
            print(f"[{arch} x {shape}] FAIL: {type(e).__name__}: {e}")
            traceback.print_exc(limit=8)
            failures.append((arch, shape, f"{type(e).__name__}: {e}"))
    print(f"\ndone: {len(combos) - len(failures) - len(skips)} ok, "
          f"{len(skips)} skipped, {len(failures)} failed")
    if failures:
        for f_ in failures:
            print("  FAIL:", f_)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
