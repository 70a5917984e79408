"""The training hot path: per-round wall time + jit dispatches, loop vs fused.

Starts the perf trajectory for the round hot path (DESIGN.md §7): for each
cohort size H, run the same arm/config through the legacy per-participant
contribution loop (``fused_rounds=False``) and the fused cohort round-step
(default), and report

  * marginal wall-clock per round — measured as
    ``(T(r_hi) - T(r_lo)) / (r_hi - r_lo)`` over two fresh runs, so one-time
    costs (jit compilation, arm construction, leader-schedule setup) cancel
    and the number is the steady-state per-round cost;
  * jit program launches per round, from the ``instrumented_jit`` counter in
    ``repro.arms.fused`` — O(H) on the loop path, O(1) on the fused path.

Every non-SecAgg fused cell also runs SPMD on the ``shard`` backend and the
report gains a ``shard`` column (``shard_vs_ideal`` wall ratio per cell) —
the trajectory record for carrying the fused path onto the pod fast path.
On the CPU, shard cells are measured in a SUBPROCESS that forces
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for itself: forcing
host devices in the *parent* would split the XLA CPU thread pool and slow
every baseline cell, silently breaking the artifact's comparability with
earlier trajectory points.  On forced host devices the "mesh" shares one
CPU's cores, so the shard ratio records collective overhead, not a speedup
claim.  Off the CPU, shard cells run in this process over the real devices
(a child cannot use the accelerator the parent holds), and fail loudly
where there are fewer than two.

``python benchmarks/hotpath.py`` writes ``BENCH_hotpath.json`` (the
committed artifact).  ``--smoke`` runs tiny shapes and *asserts* the fused
path's dispatch count is O(1) per round (on every backend measured) — the
CI perf-smoke job's contract.

``--capacity`` runs the transformer capacity column (DESIGN.md §12): the
"lm" model-size ladder through decaph with ghost clipping vs the faithful
per-example path, writing ``BENCH_capacity.json`` + ``BENCH_capacity.md``.
Each row carries the marginal wall/round, dispatches/round (the ghost cell
must be EXACTLY one — also asserted by ``--smoke``), the fused step's AOT
memory high-water from ``compiled.memory_analysis()`` (where the faithful
path's per-example gradient materialisation shows up as temp bytes the
ghost path never allocates), and a %-of-roofline column from
``repro.launch.roofline.dp_round_roofline`` — a TPU-v5e hardware-model
figure on a CPU host, the same convention the serve BENCH rows use.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import jax

import repro.arms as arms
from repro.arms import backends as backends_lib
from repro.arms import fused
from repro.compile_cache import enable_compilation_cache
from repro.core.dp import DPConfig
from repro.data.synthetic import make_gemini_like
from repro.models.tabular import linear_model

# the small tabular preset (scenarios preset "gemini/small": 32-feature
# linear model), sized so every silo draws a real Poisson batch each round
FEATURES = 32
EXAMPLES_PER_SILO = 240


def _make_setup(h: int, seed: int = 0):
    silos = arms.normalize_participants(
        make_gemini_like(seed=seed, n_total=EXAMPLES_PER_SILO * h,
                         n_silos=h, n_features=FEATURES)
    )
    return linear_model(FEATURES), silos


def _cfg(rounds: int, use_secagg: bool, fused_rounds: bool) -> arms.ArmConfig:
    return arms.ArmConfig(
        rounds=rounds, batch_size=64, lr=0.3, seed=0,
        use_secagg=use_secagg, fused_rounds=fused_rounds,
        dp=DPConfig(clip_norm=1.0, noise_multiplier=0.8, microbatch_size=8),
    )


def _run_once(arm: str, model, silos, cfg,
              backend: str = backends_lib.DEFAULT_BACKEND
              ) -> tuple[float, int, int]:
    """(wall seconds, jit dispatches, rounds completed) for one fresh run."""
    fused.reset_jit_dispatches()
    t0 = time.perf_counter()
    rep = arms.run(arm, model, silos, cfg, backend=backend)
    dt = time.perf_counter() - t0
    return dt, fused.jit_dispatches(), rep.rounds_completed


def measure(arm: str, h: int, *, use_secagg: bool, fused_rounds: bool,
            r_lo: int, r_hi: int, repeats: int,
            backend: str = backends_lib.DEFAULT_BACKEND) -> dict:
    """Marginal per-round wall/dispatch cost for one (arm, H, path) cell."""
    model, silos = _make_setup(h)
    # compile warmup: a fresh arm per run re-traces, so prime the XLA-level
    # caches for both round counts before timing
    _run_once(arm, model, silos, _cfg(2, use_secagg, fused_rounds), backend)
    t_los, t_his, disps = [], [], []
    n_lo = n_hi = 0
    for _ in range(repeats):
        t_lo, d_lo, n_lo = _run_once(
            arm, model, silos, _cfg(r_lo, use_secagg, fused_rounds), backend)
        t_hi, d_hi, n_hi = _run_once(
            arm, model, silos, _cfg(r_hi, use_secagg, fused_rounds), backend)
        if n_hi <= n_lo:
            raise RuntimeError(f"{arm} H={h}: no marginal rounds measured")
        t_los.append(t_lo)
        t_his.append(t_hi)
        disps.append((d_hi - d_lo) / (n_hi - n_lo))
    # interference only ever ADDS time, so min-of-repeats per ENDPOINT
    # converges on each clean total from above; differencing the minima
    # then cancels compile/setup.  (Differencing per pair and min-ing the
    # marginals — the earlier estimator — keeps a stall-deflated sample
    # whenever the short run stalls: observed as impossible sub-dispatch
    # cells like 27 µs/round on this container.)  A non-positive marginal
    # means every repeat of one endpoint was corrupted: record the cell as
    # unmeasured (null) rather than fabricating a number.
    wall = (min(t_his) - min(t_los)) / (n_hi - n_lo)
    return {
        "arm": arm,
        "hospitals": h,
        "use_secagg": use_secagg,
        "backend": backend,
        "path": "fused" if fused_rounds else "loop",
        "wall_per_round_s": wall if wall > 0 else None,
        "dispatches_per_round": min(disps),
    }


CELLS = [  # (arm, use_secagg) — the round arms the fused path covers
    ("decaph", True),
    ("decaph", False),
    ("fl", False),
    ("fedprox", False),
]

_SHARD_DEVICES = 8


def _measure_shard_cell(arm: str, h: int, r_lo: int, r_hi: int,
                        repeats: int) -> dict:
    """One shard cell on the CPU, measured in a subprocess that forces its
    own host devices — the parent process stays unflagged so baseline cells
    keep the full XLA CPU thread pool (trajectory comparability).  Off the
    CPU the cell runs in this process over the real devices: a child could
    not use the accelerator the parent holds."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_SHARD_DEVICES}"
    )
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                       "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    spec = json.dumps({"arm": arm, "h": h, "r_lo": r_lo, "r_hi": r_hi,
                       "repeats": repeats})
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--shard-cell", spec],
        env=env, capture_output=True, text=True, timeout=1800,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"shard cell {arm}/h{h} failed:\n{proc.stderr[-2000:]}"
        )
    payload = [ln for ln in proc.stdout.splitlines()
               if ln.startswith("ROW")][-1]
    return json.loads(payload[len("ROW"):])


def collect(hs: list[int], r_lo: int, r_hi: int, repeats: int,
            progress=lambda msg: None) -> dict:
    rows = []
    for h in hs:
        for arm, secagg in CELLS:
            plans = [(backends_lib.DEFAULT_BACKEND, False),
                     (backends_lib.DEFAULT_BACKEND, True)]
            if not secagg:
                # the SPMD column: fused only (shard has no loop path), and
                # never under SecAgg (the capabilities rule the pair out)
                plans.append(("shard", True))
            for backend, fused_rounds in plans:
                if backend == "shard" and jax.default_backend() == "cpu":
                    row = _measure_shard_cell(arm, h, r_lo, r_hi, repeats)
                else:
                    row = measure(arm, h, use_secagg=secagg,
                                  fused_rounds=fused_rounds,
                                  r_lo=r_lo, r_hi=r_hi, repeats=repeats,
                                  backend=backend)
                rows.append(row)
                wall = row["wall_per_round_s"]
                progress(
                    f"{arm:8s} H={h:<3d} secagg={str(secagg):5s} "
                    f"{backend:5s} {row['path']:5s} "
                    + (f"{wall*1e3:8.2f} ms/round" if wall is not None
                       else "  (unmeasured: interference)")
                    + f" {row['dispatches_per_round']:6.1f} disp/round"
                )
    speedups = {}
    for h in hs:
        for arm, secagg in CELLS:
            cell_rows = [
                r for r in rows
                if r["arm"] == arm and r["hospitals"] == h
                and r["use_secagg"] == secagg
            ]
            pair = {r["path"]: r for r in cell_rows
                    if r["backend"] == backends_lib.DEFAULT_BACKEND}
            shard = next((r for r in cell_rows if r["backend"] == "shard"),
                         None)
            key = f"{arm}{'-secagg' if secagg else ''}-h{h}"
            f_wall = pair["fused"]["wall_per_round_s"]
            l_wall = pair["loop"]["wall_per_round_s"]
            entry = {
                # null when either side went unmeasured — never fabricated
                "speedup": (l_wall / f_wall
                            if f_wall is not None and l_wall is not None
                            else None),
                "loop_dispatches": pair["loop"]["dispatches_per_round"],
                "fused_dispatches": pair["fused"]["dispatches_per_round"],
            }
            if shard is not None:
                s_wall = shard["wall_per_round_s"]
                entry["shard_wall_per_round_s"] = s_wall
                entry["shard_dispatches"] = shard["dispatches_per_round"]
                # > 1 means the mesh run pays that factor over single-device
                # ideal; on forced host devices this records collective
                # overhead, not a speedup claim
                entry["shard_vs_ideal"] = (
                    s_wall / f_wall
                    if s_wall is not None and f_wall is not None else None
                )
            speedups[key] = entry
    return {
        "preset": "small-tabular (gemini/small: 32-feature linear model)",
        "rounds_marginal": [r_lo, r_hi],
        "repeats": repeats,
        "shard_devices": _SHARD_DEVICES,
        "rows": rows,
        "speedups": speedups,
    }


# ---------------------------------------------------------------------------
# Transformer capacity column (DESIGN.md §12): ghost vs faithful clipping
# over the "lm" model-size ladder.
# ---------------------------------------------------------------------------

LM_SIZES = ["small", "medium", "full"]
LM_HOSPITALS = 4        # divides the debug pod mesh's ("pod","data") extent
LM_N_PER = 32           # examples per silo; rate*n_per keeps batches real
LM_BATCH = 16


def _lm_setup(model_size: str, seed: int = 0):
    from repro.scenarios import presets as presets_lib
    from repro.serve.federation import token_silos, transformer_model

    model_cfg = presets_lib.lm_model_config(model_size)
    seq_len = presets_lib.lm_seq_len(model_size)
    model = transformer_model(model_cfg)
    silos = token_silos(model_cfg, hospitals=LM_HOSPITALS, n_per=LM_N_PER,
                        seq_len=seq_len, seed=seed)
    return model_cfg, seq_len, model, silos


def _lm_cfg(rounds: int, clipping: str) -> arms.ArmConfig:
    return arms.ArmConfig(
        rounds=rounds, batch_size=LM_BATCH, lr=0.1, seed=0,
        use_secagg=False, fused_rounds=True, clipping=clipping,
        dp=DPConfig(clip_norm=1.0, noise_multiplier=0.8, microbatch_size=8),
    )


def _lm_memory(model, seq_len: int, clipping: str, pad: int) -> dict:
    """AOT memory high-water of the fused clipped-grad-sum for one silo.

    The faithful path's per-example gradient materialisation is visible
    here as temp bytes; the ghost path never allocates it.  Shapes match
    the arm's real fused step: the Poisson-padded [pad, seq] batch.
    """
    import jax
    import jax.numpy as jnp

    from repro.arms import clipping as clipping_lib
    from repro.launch import roofline

    fn = clipping_lib.clipped_grad_sum_fn(model, _lm_cfg(1, clipping), pad)
    params = model.init_fn(jax.random.PRNGKey(0))
    batch = {"x": jnp.zeros((pad, seq_len), jnp.int32),
             "y": jnp.zeros((pad, seq_len), jnp.int32)}
    mask = jnp.ones((pad,), jnp.float32)
    compiled = jax.jit(fn).lower(params, batch, mask).compile()
    mem = roofline.analyze_compiled(compiled)["memory_analysis"]
    if "error" in mem:
        return {"error": mem["error"]}
    high_water = sum(mem.get(k, 0) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes"))
    return {
        "temp_bytes": mem.get("temp_size_in_bytes"),
        "argument_bytes": mem.get("argument_size_in_bytes"),
        "output_bytes": mem.get("output_size_in_bytes"),
        "high_water_bytes": high_water,
    }


def measure_capacity_cell(model_size: str, clipping: str, *, r_lo: int,
                          r_hi: int, repeats: int) -> dict:
    """One (model size, clipping path) cell of the capacity column."""
    import jax
    import numpy as np

    from repro.arms.base import default_pad
    from repro.launch import roofline

    model_cfg, seq_len, model, silos = _lm_setup(model_size)
    rate = LM_BATCH / (LM_N_PER * LM_HOSPITALS)
    pad = default_pad(rate, silos, _lm_cfg(2, clipping))
    params = model.init_fn(jax.random.PRNGKey(0))
    n_params = int(sum(np.prod(np.shape(leaf)) or 1
                       for leaf in jax.tree_util.tree_leaves(params)))

    _run_once("decaph", model, silos, _lm_cfg(2, clipping))  # compile warmup
    t_los, t_his, disps = [], [], []
    n_lo = n_hi = 0
    for _ in range(repeats):
        t_lo, d_lo, n_lo = _run_once("decaph", model, silos,
                                     _lm_cfg(r_lo, clipping))
        t_hi, d_hi, n_hi = _run_once("decaph", model, silos,
                                     _lm_cfg(r_hi, clipping))
        if n_hi <= n_lo:
            raise RuntimeError(f"lm/{model_size}: no marginal rounds")
        t_los.append(t_lo)
        t_his.append(t_hi)
        disps.append((d_hi - d_lo) / (n_hi - n_lo))
    wall = (min(t_his) - min(t_los)) / (n_hi - n_lo)
    row = {
        "model_size": model_size,
        "clipping": clipping,
        "seq_len": seq_len,
        "model_params": n_params,
        "hospitals": LM_HOSPITALS,
        "batch_size": LM_BATCH,
        "pad": pad,
        "wall_per_round_s": wall if wall > 0 else None,
        "dispatches_per_round": min(disps),
        "memory": _lm_memory(model, seq_len, clipping, pad),
    }
    row.update(roofline.dp_round_roofline(
        model_cfg, cohort=LM_HOSPITALS, batch_per_silo=LM_BATCH,
        seq_len=seq_len, wall_seconds=row["wall_per_round_s"],
        clipping=clipping,
    ))
    return row


def collect_capacity(sizes: list[str], r_lo: int, r_hi: int, repeats: int,
                     progress=lambda msg: None) -> dict:
    rows = []
    for size in sizes:
        for clipping in ("ghost", "per-example"):
            row = measure_capacity_cell(size, clipping, r_lo=r_lo,
                                        r_hi=r_hi, repeats=repeats)
            rows.append(row)
            wall = row["wall_per_round_s"]
            progress(
                f"lm/{size:6s} {clipping:11s} "
                + (f"{wall*1e3:9.2f} ms/round" if wall is not None
                   else "  (unmeasured)")
                + f" {row['dispatches_per_round']:4.1f} disp/round"
                + (f" {row['pct_of_roofline']:.3f}%-roofline"
                   if "pct_of_roofline" in row else "")
            )
    speedups = {}
    for size in sizes:
        pair = {r["clipping"]: r for r in rows if r["model_size"] == size}
        g, f = pair["ghost"], pair["per-example"]
        g_wall, f_wall = g["wall_per_round_s"], f["wall_per_round_s"]
        g_mem = g["memory"].get("high_water_bytes")
        f_mem = f["memory"].get("high_water_bytes")
        speedups[size] = {
            "speedup": (f_wall / g_wall
                        if g_wall is not None and f_wall is not None
                        else None),
            "ghost_dispatches": g["dispatches_per_round"],
            "faithful_dispatches": f["dispatches_per_round"],
            "ghost_high_water_bytes": g_mem,
            "faithful_high_water_bytes": f_mem,
            "memory_ratio": (f_mem / g_mem if g_mem and f_mem else None),
            # the hardware-model column: faithful is memory-bound on per-
            # example grad traffic on the TPU roofline, ghost compute-bound
            "projected_tpu_speedup": (
                f["roofline_round_s"] / g["roofline_round_s"]
                if "roofline_round_s" in g and "roofline_round_s" in f
                else None),
        }
    return {
        "preset": ("lm transformer ladder (dense decoder stacks, untied "
                   "embeddings; decaph, ideal backend)"),
        "hospitals": LM_HOSPITALS,
        "batch_size": LM_BATCH,
        "examples_per_silo": LM_N_PER,
        "rounds_marginal": [r_lo, r_hi],
        "repeats": repeats,
        "roofline_target": "TPU-v5e (hardware-model figure on CPU hosts)",
        "rows": rows,
        "speedups": speedups,
    }


def capacity_markdown(report: dict) -> str:
    """BENCH_capacity.md — the human-readable capacity table."""
    lines = [
        "# Capacity: ghost-clipped fused rounds on the lm transformer ladder",
        "",
        f"decaph, ideal backend, H={report['hospitals']}, "
        f"batch={report['batch_size']}/silo, marginal rounds "
        f"{report['rounds_marginal']}, repeats={report['repeats']}.  "
        "%-of-roofline and the roofline round time are TPU-v5e "
        "hardware-model figures (`repro.launch.roofline.dp_round_roofline`); "
        "memory high-water is the fused clipped-grad-sum step's AOT "
        "`compiled.memory_analysis()` (argument + output + temp bytes).",
        "",
        "On this benchmark's CPU host both clipping paths are compute-bound, "
        "so the measured speedup understates the hardware story: on the TPU "
        "roofline the faithful path is **memory-bound** on per-example "
        "gradient traffic (8·N·B bytes/round it must write then re-read) "
        "while the ghost path never materialises a per-example gradient and "
        "stays compute-bound — the projected column below.",
        "",
        "| model | params | seq | clipping | ms/round | disp/round "
        "| %-roofline | roofline ms | bound | high-water MiB |",
        "|---|---:|---:|---|---:|---:|---:|---:|---|---:|",
    ]
    for r in report["rows"]:
        wall = r["wall_per_round_s"]
        hw = r["memory"].get("high_water_bytes")
        lines.append(
            f"| {r['model_size']} | {r['model_params']:,} | {r['seq_len']} "
            f"| {r['clipping']} "
            + (f"| {wall*1e3:.2f} " if wall is not None else "| n/a ")
            + f"| {r['dispatches_per_round']:.1f} "
            + (f"| {r['pct_of_roofline']:.3f} "
               if "pct_of_roofline" in r else "| n/a ")
            + f"| {r['roofline_round_s']*1e3:.3f} "
            + f"| {r['roofline_bottleneck']} "
            + (f"| {hw/2**20:.1f} |" if hw is not None else "| n/a |")
        )
    lines += ["",
              "| model | measured speedup | projected TPU speedup "
              "| memory ratio (faithful/ghost) |",
              "|---|---:|---:|---:|"]
    for size, s in report["speedups"].items():
        sp = f"{s['speedup']:.2f}x" if s["speedup"] is not None else "n/a"
        pj = (f"{s['projected_tpu_speedup']:.2f}x"
              if s["projected_tpu_speedup"] is not None else "n/a")
        mr = (f"{s['memory_ratio']:.2f}x"
              if s["memory_ratio"] is not None else "n/a")
        lines.append(f"| {size} | {sp} | {pj} | {mr} |")
    lines.append("")
    return "\n".join(lines)


def _capacity_failures(report: dict) -> list[str]:
    """The §12 dispatch contract over capacity rows: a ghost fused round is
    EXACTLY one program launch — not O(1), one.  The faithful path stays
    fused too (the microbatch loop lives inside the program), so it gets
    the same O(1) bound the tabular cells assert."""
    failures = []
    for r in report["rows"]:
        disp = r["dispatches_per_round"]
        key = f"lm/{r['model_size']}/{r['clipping']}"
        if r["clipping"] == "ghost" and disp != 1.0:
            failures.append(
                f"{key}: {disp:.2f} dispatches/round (expected exactly 1)"
            )
        elif r["clipping"] == "per-example" and disp > 2.0:
            failures.append(
                f"{key}: {disp:.2f} dispatches/round (expected O(1))"
            )
    return failures


def run(fast: bool = True) -> list[dict]:
    """benchmarks/run.py entry point."""
    hs = [5, 10] if fast else [5, 10, 20]
    report = collect(hs, r_lo=3, r_hi=9 if fast else 15, repeats=1,
                     progress=lambda m: print(m, file=sys.stderr))
    return [
        {
            "name": (f"hotpath_{r['arm']}_h{r['hospitals']}"
                     f"{'_secagg' if r['use_secagg'] else ''}"
                     + (f"_{r['backend']}"
                        if r["backend"] != backends_lib.DEFAULT_BACKEND
                        else "")
                     + f"_{r['path']}"),
            "us_per_call": (r["wall_per_round_s"] or 0.0) * 1e6,
            "derived": f"dispatches_per_round={r['dispatches_per_round']:.1f}",
        }
        for r in report["rows"]
    ]


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--smoke", action="store_true",
                   help="tiny shapes; assert fused dispatches are O(1)")
    p.add_argument("--out", default="BENCH_hotpath.json")
    p.add_argument("--hospitals", type=int, nargs="+",
                   default=[5, 10, 20])
    p.add_argument("--rounds", type=int, nargs=2, default=[10, 50],
                   metavar=("R_LO", "R_HI"))
    p.add_argument("--repeats", type=int, default=5)
    p.add_argument("--capacity", action="store_true",
                   help="run the lm transformer capacity column instead "
                        "(ghost vs per-example clipping; writes "
                        "BENCH_capacity.json + .md)")
    p.add_argument("--capacity-sizes", nargs="+", default=LM_SIZES,
                   choices=LM_SIZES)
    p.add_argument("--capacity-rounds", type=int, nargs=2, default=[3, 9],
                   metavar=("R_LO", "R_HI"))
    p.add_argument("--shard-cell", help=argparse.SUPPRESS)  # subprocess mode
    args = p.parse_args(argv)
    enable_compilation_cache()

    if args.shard_cell:
        # child mode: this process was spawned with forced host devices to
        # measure exactly one shard cell; print the row and exit
        spec = json.loads(args.shard_cell)
        row = measure(spec["arm"], spec["h"], use_secagg=False,
                      fused_rounds=True, r_lo=spec["r_lo"],
                      r_hi=spec["r_hi"], repeats=spec["repeats"],
                      backend="shard")
        print("ROW" + json.dumps(row))
        return 0

    if args.capacity:
        out = (args.out if args.out != "BENCH_hotpath.json"
               else "BENCH_capacity.json")
        sizes = ["small"] if args.smoke else list(args.capacity_sizes)
        r_lo, r_hi = ([2, 5] if args.smoke else args.capacity_rounds)
        repeats = 1 if args.smoke else args.repeats
        report = collect_capacity(
            sizes, r_lo, r_hi, repeats,
            progress=lambda m: print(m, file=sys.stderr))
        failures = _capacity_failures(report)
        with open(out, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        md_out = os.path.splitext(out)[0] + ".md"
        with open(md_out, "w") as f:
            f.write(capacity_markdown(report))
        print(f"wrote {out} and {md_out}", file=sys.stderr)
        for size, s in report["speedups"].items():
            sp = (f"{s['speedup']:6.2f}x" if s["speedup"] is not None
                  else "   n/a")
            mr = (f"{s['memory_ratio']:5.2f}x"
                  if s["memory_ratio"] is not None else "  n/a")
            print(f"lm/{size:8s} ghost speedup {sp}  memory {mr}  "
                  f"dispatches {s['faithful_dispatches']:.1f} -> "
                  f"{s['ghost_dispatches']:.1f}")
        if failures:
            print("\n".join("FAIL: " + f for f in failures), file=sys.stderr)
            return 1
        return 0

    if args.smoke:
        args.hospitals, args.rounds, args.repeats = [4], [2, 6], 1

    report = collect(args.hospitals, r_lo=args.rounds[0],
                     r_hi=args.rounds[1], repeats=args.repeats,
                     progress=lambda m: print(m, file=sys.stderr))

    failures = []
    for key, s in report["speedups"].items():
        # the structural contract, asserted even in --smoke: a fused round
        # is ONE cohort program launch, a loop round is >= H of them —
        # on the SPMD backend too (the mesh must not reintroduce per-
        # participant or per-shard dispatch)
        if s["fused_dispatches"] > 2.0:
            failures.append(
                f"{key}: fused path dispatches "
                f"{s['fused_dispatches']:.1f}/round (expected O(1))"
            )
        if s.get("shard_dispatches", 0.0) > 2.0:
            failures.append(
                f"{key}: shard path dispatches "
                f"{s['shard_dispatches']:.1f}/round (expected O(1))"
            )
        if s["loop_dispatches"] < s["fused_dispatches"]:
            failures.append(f"{key}: loop path dispatched less than fused?")

    if args.smoke:
        # the CI perf-smoke contract for the ghost transformer path: one
        # fused DP round with ghost clipping is EXACTLY one program launch
        ghost_row = measure_capacity_cell("small", "ghost", r_lo=2, r_hi=5,
                                          repeats=1)
        report["ghost_smoke_cell"] = ghost_row
        disp = ghost_row["dispatches_per_round"]
        print(f"ghost-lm smoke cell: {disp:.1f} dispatches/round",
              file=sys.stderr)
        if disp != 1.0:
            failures.append(
                f"ghost transformer cell: {disp:.2f} dispatches/round "
                f"(expected exactly 1)"
            )

    with open(args.out, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    print(f"wrote {args.out}", file=sys.stderr)
    for key, s in sorted(report["speedups"].items()):
        sp = (f"{s['speedup']:6.2f}x" if s["speedup"] is not None
              else "   n/a")
        line = (f"{key:24s} speedup {sp}  "
                f"dispatches {s['loop_dispatches']:.1f} -> "
                f"{s['fused_dispatches']:.1f}")
        if s.get("shard_vs_ideal") is not None:
            line += f"  shard/ideal {s['shard_vs_ideal']:5.2f}x"
        print(line)
    if failures:
        print("\n".join("FAIL: " + f for f in failures), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
