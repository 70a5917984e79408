"""Serving-tier benchmark: open-loop traffic sweep + dispatch-count contract.

For each arrival rate the sweep replays a seeded Poisson schedule
(``repro.serve.traffic``) against a fresh ``ServeEngine`` while federation
checkpoints land mid-stream, and reports throughput, p50/p99 TTFT and
per-token latency, slot occupancy, and checkpoint freshness — the
utility-vs-epsilon-vs-freshness artifact (``BENCH_serve.json`` +
``BENCH_serve.md``, both committed).

Two structural contracts are ASSERTED (CI serve-smoke job):

  * **O(1) steady-state dispatch**: with every slot busy and no admissions,
    N decode steps are exactly N program launches — measured with the
    process-global ``instrumented_jit`` counter, the same meter DESIGN.md
    §7 pins on fused training rounds.  Additionally the whole traffic
    replay must launch exactly ``decode_steps + admit_dispatches``
    programs: continuous batching adds ZERO hidden dispatches.
  * **mid-stream hot swap**: a checkpoint published while slots are
    decoding is picked up (``swaps >= 1``) and every in-flight generation
    still completes its full budget.

Publish modes: ``--smoke`` publishes inline between decode steps
(single-threaded, deterministic — perturbed copies of the serving params);
the full sweep runs a REAL federation trainer thread per rate
(``repro.serve.federation.train_and_publish``, fl arm on the ideal
backend) so the freshness columns reflect actual round cadence.

``python benchmarks/serve_bench.py`` writes the committed artifacts;
``--smoke`` shrinks shapes and asserts the contracts above.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import threading

import jax
import numpy as np

from repro.instrument import jit_dispatches, reset_jit_dispatches
from repro.compile_cache import enable_compilation_cache
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.handoff import CheckpointPublisher, CheckpointWatcher
from repro.serve.metrics import render_markdown, summarize
from repro.serve.traffic import (
    Request,
    TrafficConfig,
    generate_requests,
    run_open_loop,
)

ARCH = "smollm-360m"


def _engine(slots: int, max_len: int, seed: int = 0) -> ServeEngine:
    return ServeEngine(ServeConfig(
        arch=ARCH, slots=slots, max_len=max_len, temperature=1.0, seed=seed,
    ))


def steady_state_contract(slots: int, max_len: int, n_steps: int = 20) -> dict:
    """The dispatch-count + hot-swap invariant, measured in isolation.

    Fills every slot, then: (a) ``n_steps`` decode steps must be EXACTLY
    ``n_steps`` program launches on the global ``instrumented_jit`` meter;
    (b) a checkpoint published mid-segment hot-swaps without costing a
    launch or dropping an in-flight generation.
    """
    engine = _engine(slots, max_len)
    budget = max_len - 8 - 1  # outlive the segment: nobody evicts mid-test
    reqs = [
        Request(rid=i, arrival=0.0,
                prompt=np.full((8,), 7 + i, np.int32),
                max_new_tokens=budget)
        for i in range(slots)
    ]
    for r in reqs:
        finished = engine.admit(r)
        assert not finished, "steady-state request must outlive admission"
    with tempfile.TemporaryDirectory() as d:
        pub = CheckpointPublisher(d)
        watcher = CheckpointWatcher(d)
        swapped_at = n_steps // 2
        reset_jit_dispatches()
        for t in range(n_steps):
            done = engine.step()
            assert not done, "no eviction may occur inside the segment"
            if t == swapped_at:
                # publish + poll between steps — the hot-swap path; the
                # publish itself is host-side msgpack, zero device launches
                pub.publish(0, jax.tree_util.tree_map(
                    lambda x: x * 0.999, engine.params))
                assert engine.poll_watcher(watcher), "swap must land"
        launches = jit_dispatches()
    assert launches == n_steps, (
        f"steady-state contract violated: {n_steps} decode steps took "
        f"{launches} program launches (expected exactly {n_steps})"
    )
    assert engine.swaps == 1 and engine.serving_round == 0
    for r in reqs:
        # in-flight generations crossed the swap intact: every step
        # appended a token to every slot
        assert len(r.tokens) == 1 + n_steps
    return {"steps": n_steps, "launches": launches, "swaps": engine.swaps}


def _inline_publisher(engine: ServeEngine, pub: CheckpointPublisher,
                      every: int):
    """Deterministic smoke-mode publisher: every ``every``-th decode step
    publishes a perturbed copy of the serving params as the next round."""
    state = {"round": 0}

    def on_step(step_idx: int) -> None:
        if step_idx % every == every - 1:
            pub.publish(state["round"], jax.tree_util.tree_map(
                lambda x: x * 0.999, engine.params))
            state["round"] += 1

    return on_step


def measure_rate(rate: float, *, slots: int, max_len: int, requests: int,
                 smoke: bool, seed: int = 0) -> dict:
    engine = _engine(slots, max_len, seed=seed)
    tcfg = TrafficConfig(rate=rate, n_requests=requests,
                         vocab_size=engine.model_cfg.vocab_size, seed=seed)
    reqs = generate_requests(tcfg)
    with tempfile.TemporaryDirectory() as d:
        watcher = CheckpointWatcher(d)
        on_step, trainer = None, None
        if smoke:
            on_step = _inline_publisher(engine, CheckpointPublisher(d),
                                        every=5)
        else:
            from repro.serve.federation import train_and_publish

            # paced: at smoke scale a round is sub-ms, so without pacing
            # the watcher would only ever see the final round land
            trainer = threading.Thread(
                target=train_and_publish,
                args=("fl", engine.model_cfg, d),
                kwargs={"rounds": 6, "seed": seed, "pace_s": 0.5},
                daemon=True,
            )
            trainer.start()
        reset_jit_dispatches()
        result = run_open_loop(engine, reqs, watcher=watcher,
                               poll_interval=0.02, on_step=on_step)
        launches = jit_dispatches()
        if trainer is not None:
            trainer.join(timeout=120.0)
    row = summarize(result, slots=slots, rate=rate, extra={
        "publish_mode": "inline" if smoke else "federation-thread",
    })
    if smoke:
        # the trainer thread shares the global meter in full mode, so the
        # zero-hidden-dispatch ledger is only checkable inline
        expected = result.decode_dispatches + result.admit_dispatches
        assert launches == expected, (
            f"rate {rate}: traffic replay launched {launches} programs, "
            f"ledger says {expected} (decode + admit) — hidden dispatches"
        )
        assert row["dispatches_per_step"] == 1.0, row
        assert row["swaps"] >= 1, f"rate {rate}: no mid-stream hot swap"
        incomplete = [
            r for r in result.completed
            if len(r.tokens) < min(r.max_new_tokens,
                                   max_len - len(r.prompt))
            and (engine.cfg.eos_id is None
                 or engine.cfg.eos_id not in r.tokens)
        ]
        assert not incomplete, (
            f"rate {rate}: {len(incomplete)} generations dropped tokens "
            "across a hot swap"
        )
    return row


def collect(rates, *, slots: int, max_len: int, requests: int, smoke: bool,
            progress=lambda m: None) -> dict:
    contract = steady_state_contract(slots, max_len)
    progress(f"steady-state contract: {contract['steps']} steps = "
             f"{contract['launches']} launches, {contract['swaps']} swap")
    rows = []
    for rate in rates:
        row = measure_rate(rate, slots=slots, max_len=max_len,
                           requests=requests, smoke=smoke)
        rows.append(row)
        progress(f"rate {rate:6.1f} q/s: {row['throughput_tok_s']:8.1f} tok/s"
                 f"  TTFT p99 {row['ttft_p99_ms']:9.1f} ms"
                 f"  occ {row['occupancy']:.2f}  swaps {row['swaps']}"
                 f"  stale(mean) {row['staleness_rounds_mean']}")
    return {
        "arch": ARCH,
        "scale": "smoke",
        "slots": slots,
        "max_len": max_len,
        "n_requests": requests,
        "publish_mode": "inline" if smoke else "federation-thread",
        "steady_state": contract,
        "rows": rows,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--smoke", action="store_true",
                   help="tiny shapes; assert the dispatch + hot-swap "
                        "contracts; inline (single-threaded) publishing")
    p.add_argument("--out", default="BENCH_serve.json")
    p.add_argument("--md", default=None,
                   help="markdown report path (default: --out with .md)")
    p.add_argument("--rates", type=float, nargs="+",
                   default=[2.0, 8.0, 32.0])
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--max-len", type=int, default=96)
    p.add_argument("--requests", type=int, default=40)
    args = p.parse_args(argv)
    enable_compilation_cache()

    if args.smoke:
        args.rates, args.slots = [4.0, 16.0], 2
        args.max_len, args.requests = 48, 10

    report = collect(args.rates, slots=args.slots, max_len=args.max_len,
                     requests=args.requests, smoke=args.smoke,
                     progress=lambda m: print(m, file=sys.stderr))

    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    md_path = args.md or (args.out.rsplit(".", 1)[0] + ".md")
    publish_how = ("inline" if args.smoke
                   else "by a live federation trainer (fl, 6 rounds)")
    preamble = (
        f"Arch `{report['arch']}` (smoke scale), {report['slots']} slots, "
        f"max_len {report['max_len']}, {report['n_requests']} Poisson "
        f"arrivals per rate; checkpoints published {publish_how} and "
        f"hot-swapped mid-stream.  Steady-state contract: "
        f"{report['steady_state']['steps']} decode steps = "
        f"{report['steady_state']['launches']} program launches."
    )
    with open(md_path, "w") as f:
        f.write(render_markdown(
            report["rows"],
            title="BENCH_serve — continuous batching under open-loop traffic",
            preamble=preamble,
        ))
    print(f"wrote {args.out} and {md_path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
