"""``python -m repro.serve`` — live serving demo / single-rate harness run.

Examples::

    # open-loop traffic against seed weights, smoke-scale model
    python -m repro.serve --arch smollm-360m --rate 4 --slots 4

    # serve while WATCHING a checkpoint directory someone else publishes to
    python -m repro.serve --watch /tmp/ckpts --rate 2

    # the full loop in one process: a federation trainer thread publishes
    # round checkpoints that the engine hot-swaps mid-traffic
    python -m repro.serve --train-rounds 6 --arm fl --rate 4

The multi-rate sweep that writes the committed ``BENCH_serve.json`` lives
in ``benchmarks/serve_bench.py``.
"""

from __future__ import annotations

import argparse
import json
import tempfile
import threading

import repro.obs as obs
from repro.compile_cache import enable_compilation_cache
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.handoff import CheckpointWatcher
from repro.serve.metrics import render_markdown, summarize
from repro.serve.traffic import TrafficConfig, generate_requests, run_open_loop


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m repro.serve",
        description="continuous-batching serving demo fed by federation "
                    "checkpoints",
    )
    p.add_argument("--arch", default="smollm-360m",
                   help="decoder-only arch name (repro.configs)")
    p.add_argument("--rate", type=float, default=4.0,
                   help="mean Poisson arrival rate, requests/second")
    p.add_argument("--slots", type=int, default=4,
                   help="fixed decode-batch width")
    p.add_argument("--max-len", type=int, default=96,
                   help="per-slot KV capacity (prompt + generation)")
    p.add_argument("--requests", type=int, default=32,
                   help="number of arrivals to replay")
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--full", action="store_true",
                   help="full paper-scale config instead of smoke scale")
    p.add_argument("--watch", default=None, metavar="DIR",
                   help="hot-swap checkpoints published into DIR")
    p.add_argument("--train-rounds", type=int, default=0, metavar="N",
                   help="also run an in-process federation trainer thread "
                        "publishing N rounds (into --watch, or a temp dir)")
    p.add_argument("--arm", default="fl",
                   help="federation arm for --train-rounds")
    p.add_argument("--json", default=None, metavar="PATH",
                   help="write the summary row as JSON")
    p.add_argument("--obs", default=None, metavar="DIR",
                   help="record obs spans/counters for the whole run and "
                        "export events + ledger + Chrome trace into DIR")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    enable_compilation_cache()
    rec = obs.enable() if args.obs else None
    engine = ServeEngine(ServeConfig(
        arch=args.arch, slots=args.slots, max_len=args.max_len,
        temperature=args.temperature, seed=args.seed, smoke=not args.full,
    ))
    watch_dir = args.watch
    trainer = None
    if args.train_rounds > 0:
        if watch_dir is None:
            watch_dir = tempfile.mkdtemp(prefix="repro-serve-ckpt-")
        from repro.serve.federation import train_and_publish

        # the trainer MUST train the arch being served: hot-swap relies on
        # identical parameter shapes (same compiled decode program)
        trainer = threading.Thread(
            target=train_and_publish,
            args=(args.arm, engine.model_cfg, watch_dir),
            kwargs={"rounds": args.train_rounds, "seed": args.seed,
                    "pace_s": 0.5},
            daemon=True,
        )
        trainer.start()
        print(f"trainer: {args.arm} x {args.train_rounds} rounds "
              f"-> {watch_dir}")
    watcher = CheckpointWatcher(watch_dir) if watch_dir else None

    tcfg = TrafficConfig(rate=args.rate, n_requests=args.requests,
                         vocab_size=engine.model_cfg.vocab_size,
                         seed=args.seed)
    requests = generate_requests(tcfg)
    print(f"serving {args.arch} ({'full' if args.full else 'smoke'} scale): "
          f"{args.requests} requests @ {args.rate} q/s, "
          f"{args.slots} slots, max_len {args.max_len}")
    result = run_open_loop(engine, requests, watcher=watcher)
    if trainer is not None:
        trainer.join(timeout=60.0)
    row = summarize(result, slots=args.slots, rate=args.rate,
                    extra={"arch": args.arch})
    print(render_markdown([row], title="repro.serve — single run"))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(row, f, indent=2)
        print(f"wrote {args.json}")
    if rec is not None:
        paths = obs.export(args.obs, rec)
        obs.disable()
        print(f"obs: wrote {', '.join(str(v) for v in paths.values())}")
    return 0
