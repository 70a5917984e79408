"""Drive the system's main paths once on a TPU and check what comes out.

    python chip_smoke.py             # one chip: train, train-ghost, serve
    python chip_smoke.py --chips 4   # four chips: shard vs ideal, only that

One chip runs three phases through the entry points a user calls:

* ``train``: a DeCaPH round at the paper's published widths (the
  ``pancreas-full`` preset, MLP 15558-1000-100-4, 5 hospitals, SecAgg on),
  3 rounds through ``repro.scenarios.executor`` — the code
  ``python -m repro.scenarios --run`` executes, without its result cache;
* ``train-ghost``: the ``lm-full`` transformer preset with ghost clipping,
  2 rounds — the path on which the ``ghost_norm`` Pallas kernel runs; the
  compiled fused-round text must contain the kernel (``tpu_custom_call``);
* ``serve``: smollm-360m at its published widths (random weights) behind
  ``ServeEngine`` with the ``decode_attention`` kernel, 8 open-loop requests.

Each phase checks its own invariants: rounds completed, ε equal to an
independent RDP accountant, finite loss and parameters; every request served
its full token budget at exactly one dispatch per decode step.  ``--chips 4``
runs ``lm-full`` DeCaPH on the ``shard`` backend with its 4 hospitals over
the 4 chips, and the same cell on ``ideal`` in this process: params agree
within atol 1e-5 and ε is bit-equal.

There is no CPU fallback: without a TPU the script exits non-zero and prints
no result.  Any failed check or exception ends the process non-zero.  The
last line of stdout is the JSON result.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

TRAIN_ROUNDS = 3
GHOST_ROUNDS = 2
SHARD_ROUNDS = 3
SERVE_REQUESTS = 8
SHARD_ATOL = 1e-5


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, process-wide."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self) -> None:
        from jax import monitoring

        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, duration, **_kw) -> None:
        if name in self.EVENTS:
            self.seconds += duration


class Phase:
    """Times one phase and prints its closing line with the device."""

    def __init__(self, name: str, clock: CompileClock, device: dict) -> None:
        self.name, self.clock, self.device = name, clock, device

    def say(self, msg: str) -> None:
        print(f"[{self.name}] {msg}", flush=True)

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), self.clock.seconds
        self.say(f"start on {self.device['platform']} "
                 f"{self.device['kind']} x{self.device['count']}")
        return self

    def __exit__(self, exc_type, exc, tb):
        wall = time.perf_counter() - self.t0
        compile_s = self.clock.seconds - self.c0
        status = "ok" if exc_type is None else f"FAILED ({exc_type.__name__})"
        self.say(f"{status}: wall {wall:.3f} s = compile {compile_s:.3f} s "
                 f"+ run {wall - compile_s:.3f} s "
                 f"[{self.device['platform']} {self.device['kind']} "
                 f"x{self.device['count']}]")
        return False


class ProgramText:
    """Executor for ``repro.instrument.execution_context``: runs each program
    as plain jit (what the ``ideal`` backend does) and keeps the compiled
    text of every distinct program it dispatched."""

    def __init__(self) -> None:
        self.texts: dict[str, str] = {}

    def round_pad(self, pad: int) -> int:
        return pad

    def mark(self, arr, axis: int) -> None:
        pass

    def execute(self, wrapper, args, kwargs):
        name = getattr(wrapper.fn, "__name__", "<fn>")
        if name not in self.texts:
            lowered = wrapper.jitted.lower(*args, **kwargs)
            self.texts[name] = lowered.compile().as_text()
        return wrapper.jitted(*args, **kwargs)


def check_training(p: Phase, spec, row, rep, silos_total: int) -> None:
    import jax
    import numpy as np

    from repro.core.accountant import compute_epsilon
    from repro.core.dp import DPConfig

    eps_ref = compute_epsilon(spec.batch_size / silos_total,
                              spec.noise_multiplier, rep.rounds_completed,
                              DPConfig().delta)
    p.say(f"rounds {row['rounds_completed']}/{spec.rounds}, "
          f"epsilon {row['epsilon']!r} (accountant {eps_ref!r}), "
          f"mean loss {row['mean_loss']!r}, params {row['model_params']}, "
          f"pooled accuracy {row['accuracy']!r}")
    check(row["rounds_completed"] == spec.rounds,
          f"{row['rounds_completed']} of {spec.rounds} rounds completed")
    check(row["epsilon"] > 0, "epsilon is not positive")
    check(math.isclose(row["epsilon"], eps_ref, rel_tol=1e-9),
          f"epsilon {row['epsilon']!r} != accountant {eps_ref!r}")
    check(row["mean_loss"] is not None and math.isfinite(row["mean_loss"]),
          f"mean loss {row['mean_loss']!r} is not finite")
    bad = [i for i, leaf in enumerate(jax.tree_util.tree_leaves(rep.params))
           if not np.isfinite(np.asarray(leaf)).all()]
    check(not bad, f"param leaves {bad} hold non-finite values")


def _cohort_size(spec) -> int:
    from repro.scenarios import presets

    return sum(len(p) for p in presets.build_silos(spec))


def phase_train(clock, device) -> None:
    from repro.scenarios.executor import run_spec_report
    from repro.scenarios.presets import get_preset

    spec = get_preset("pancreas-full").replace(
        arm="decaph", backend="ideal", use_secagg=True, rounds=TRAIN_ROUNDS)
    with Phase("train", clock, device) as p:
        p.say(f"pancreas-full: MLP 15558-1000-100-4, H={spec.hospitals}, "
              f"{spec.examples} examples, batch {spec.batch_size}, "
              f"SecAgg on, {spec.rounds} rounds")
        row, rep = run_spec_report(spec)
        check_training(p, spec, row, rep, _cohort_size(spec))


def phase_train_ghost(clock, device) -> None:
    from repro.instrument import execution_context
    from repro.scenarios.executor import run_spec_report
    from repro.scenarios.presets import get_preset

    spec = get_preset("lm-full").replace(
        arm="decaph", backend="ideal", clipping="ghost", rounds=GHOST_ROUNDS)
    with Phase("train-ghost", clock, device) as p:
        p.say(f"lm-full: transformer, ghost clipping, H={spec.hospitals}, "
              f"{spec.rounds} rounds")
        programs = ProgramText()
        with execution_context(programs):
            row, rep = run_spec_report(spec)
        for name, text in sorted(programs.texts.items()):
            p.say(f"compiled program {name}: tpu_custom_call "
                  f"{'present' if 'tpu_custom_call' in text else 'absent'}")
        check(any("tpu_custom_call" in t for t in programs.texts.values()),
              "no compiled round program contains the ghost_norm kernel")
        check_training(p, spec, row, rep, _cohort_size(spec))


def phase_serve(clock, device) -> None:
    from repro.instrument import jit_dispatches, reset_jit_dispatches
    from repro.serve.engine import ServeConfig, ServeEngine
    from repro.serve.metrics import summarize
    from repro.serve.traffic import (Request, TrafficConfig,
                                     generate_requests, run_open_loop)

    cfg = ServeConfig(arch="smollm-360m", smoke=False, slots=4, max_len=512)
    with Phase("serve", clock, device) as p:
        engine = ServeEngine(cfg)
        mc = engine.model_cfg
        p.say(f"smollm-360m: d_model {mc.d_model}, {mc.n_layers} layers, "
              f"heads {mc.n_heads}/{mc.n_kv_heads}, vocab {mc.vocab_size}, "
              f"{cfg.slots} slots, max_len {cfg.max_len}, decode kernel "
              f"{mc.use_decode_kernel}")
        traffic = TrafficConfig(rate=50.0, n_requests=SERVE_REQUESTS,
                                vocab_size=mc.vocab_size, seed=0)
        requests = generate_requests(traffic)
        # compile every program the replay uses (one prefill per prompt
        # length, the splice, the decode step) before the clock starts
        t0, c0 = time.perf_counter(), clock.seconds
        for i, plen in enumerate(sorted({len(r.prompt) for r in requests})):
            warm = Request(rid=-1 - i, arrival=0.0,
                           prompt=requests[0].prompt[:1].repeat(plen),
                           max_new_tokens=2)
            if not engine.admit(warm):
                while engine.busy():
                    engine.step()
        p.say(f"warm-up {time.perf_counter() - t0:.3f} s "
              f"(compile {clock.seconds - c0:.3f} s)")
        reset_jit_dispatches()
        trace = run_open_loop(engine, requests, max_wall=600.0)
        launched = jit_dispatches()
        stats = summarize(trace, slots=cfg.slots, rate=traffic.rate)
        p.say(f"{len(trace.completed)}/{len(requests)} requests, "
              f"{stats['n_tokens']} tokens, {trace.decode_steps} decode steps, "
              f"{trace.decode_dispatches} decode dispatches, "
              f"{trace.admit_dispatches} admission dispatches, "
              f"{launched} programs launched")
        p.say(f"informative, {device['kind']}: TTFT p50 "
              f"{stats['ttft_p50_ms']} ms, TPOT p50 {stats['tpot_p50_ms']} ms, "
              f"{stats['throughput_tok_s']} tok/s")
        check(len(trace.completed) == len(requests),
              f"{len(trace.completed)} of {len(requests)} requests finished")
        short = [r.rid for r in requests
                 if len(r.tokens) != r.max_new_tokens]
        check(not short, f"requests {short} ended short of their budget")
        check(trace.decode_steps > 0, "no decode step ran")
        check(trace.decode_dispatches == trace.decode_steps,
              f"{trace.decode_dispatches} dispatches for "
              f"{trace.decode_steps} decode steps")
        check(launched == trace.decode_steps + trace.admit_dispatches,
              f"{launched} programs launched, expected "
              f"{trace.decode_steps} decode + {trace.admit_dispatches} "
              "admission")


def phase_shard(clock, device) -> None:
    import jax
    import numpy as np

    import repro.arms as arms
    from repro.launch.federated import ShardedRunner
    from repro.launch.mesh import make_debug_mesh
    from repro.scenarios.executor import build_scenario
    from repro.scenarios.presets import get_preset

    spec = get_preset("lm-full").replace(arm="decaph", backend="shard",
                                         rounds=SHARD_ROUNDS)
    with Phase("shard", clock, device) as p:
        model, silos, cfg, _, _ = build_scenario(spec)
        # ("pod", "data", "model") = (2, 2, 1): the 4 hospitals split over
        # the combined ("pod", "data") extent, one per chip
        mesh = make_debug_mesh(2, 1, multi_pod=True)
        p.say(f"lm-full decaph, H={spec.hospitals}, {spec.rounds} rounds, "
              f"mesh {dict(mesh.shape)}")
        runner = ShardedRunner(mesh=mesh)
        shard = runner.run(arms.get(spec.arm)(model, silos, cfg))
        ideal = arms.run(spec.arm, model, silos, cfg, backend="ideal")
        diff = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
                   for a, b in zip(jax.tree_util.tree_leaves(shard.params),
                                   jax.tree_util.tree_leaves(ideal.params)))
        layout = runner.executor.cohort_layout
        for dev, index in sorted(layout.items(), key=lambda kv: kv[0].id):
            p.say(f"cohort batch shard {index} on {dev}")
        p.say(f"max |shard - ideal| over params {diff!r} (atol {SHARD_ATOL}); "
              f"epsilon shard {shard.epsilon!r} ideal {ideal.epsilon!r}; "
              f"participant shards {runner.executor.participant_shards}")
        check(shard.rounds_completed == ideal.rounds_completed == spec.rounds,
              "rounds completed differ")
        check(diff <= SHARD_ATOL, f"params differ by {diff!r}")
        check(shard.epsilon == ideal.epsilon, "epsilon differs")
        check(len(layout) == 4 and len({str(i) for i in layout.values()}) == 4,
              f"cohort batch not split over 4 devices: {layout}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the shard-vs-ideal check on 4 chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}

    from repro.compile_cache import enable_compilation_cache

    print(f"compile cache: {enable_compilation_cache()}", flush=True)
    clock = CompileClock()
    phases = ([phase_shard] if args.chips == 4
              else [phase_train, phase_train_ghost, phase_serve])
    for phase in phases:
        phase(clock, device)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
