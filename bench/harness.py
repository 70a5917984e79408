"""One run of one benchmark cell: set-up, the measured window, the check.

A cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration file (``bench/configs``), a traffic file
(``bench/traffic/<traffic>.json``) and has a file of limits
(``bench/limits/<cell>.json``); per-layer metrics are readers in
``bench/layer_metrics/<metric>.py``.  Nothing here knows a cell by name.

A configuration's ``model.kind`` names its kind, ``bench/kinds/<kind>.py``:
the module that makes the cohort, the program's model and hospitals, the
reference's model and the operation count for that kind of model.  Keys of
the traffic file other than ``batch_size`` and ``secagg`` are the kind's.

Set-up: generate the cohort from the seed, hand the hospitals' arrays to the
program (the kind's model and preparation, ``repro.arms.run`` with the
DeCaPH arm on the ``ideal`` backend), and let the run's first
``WARMUP_ROUNDS`` rounds compile every program of the round.  The seed
draws the cohort and the initial weights; the program's own stream (its
Poisson draws, noise keys and SecAgg pads, ``ArmConfig.seed``) starts from
``STREAM_SEED`` in every run, because the program compiles the noise key
into its round step: a stream seed per run would compile that step anew
for every fresh seed and make ``setup_s`` read the compile cache's state.
``setup_s`` leaves out the benchmark's own cohort generation.  The window
opens when those rounds are done and takes whole rounds of the same run,
back to back, until the first round that ends after ``seconds``; the
parameters are then ready on the device.  The run stops through the
program's epsilon-budget check, which ``should_stop`` reads after every
stepped round.

Then the reference follows the run's first ``FOLLOWED_ROUNDS`` rounds and
``compare`` decides ``correct``.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
KINDS = BENCH / "kinds"
WARMUP_ROUNDS = 1
STREAM_SEED = 0
FOLLOWED_ROUNDS = 3
MAX_ROUNDS = 100_000
# the profiler covers the window's first rounds, up to the end of the first
# round that ends this long after the window opened
TRACE_SECONDS = 3.0


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    limits: dict
    per_layer: list[dict]


def load_cell(name: str) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, with the files it names."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name,
        config=json.loads((ROOT / conf["file"]).read_text()),
        traffic=json.loads(
            (BENCH / "traffic" / f"{w['traffic']}.json").read_text()),
        chips=w["chips"],
        limits=json.loads((BENCH / "limits" / f"{name}.json").read_text()),
        per_layer=[m for m in bench["per_layer"] if name in m["workloads"]],
    )


@functools.cache
def _load(path: Path, prefix: str):
    spec = importlib.util.spec_from_file_location(
        prefix + path.stem.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(metric: str):
    """The ``read(run)`` function of ``bench/layer_metrics/<metric>.py``."""
    return _load(BENCH / "layer_metrics" / f"{metric}.py",
                 "bench_metric_").read


def load_kind(config: dict):
    """The module ``<KINDS>/<kind>.py`` of ``config["model"]["kind"]``."""
    path = KINDS / f"{config['model']['kind']}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"no module for model kind {config['model']['kind']!r}: "
            f"looked for {path}")
    return _load(path, "bench_kind_")


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, and how often it
    traced and compiled, process-wide."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self) -> None:
        from jax import monitoring

        self.seconds, self.traces, self.compiles = 0.0, 0, 0
        self.by_event = dict.fromkeys(self.EVENTS, 0.0)
        self.cache = {"requests": 0, "hits": 0}
        monitoring.register_event_duration_secs_listener(self._on_event)
        monitoring.register_event_listener(self._on_count)

    def _on_count(self, name, **_kw) -> None:
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache["requests"] += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1

    def _on_event(self, name, duration, **_kw) -> None:
        if name in self.EVENTS:
            self.seconds += duration
            self.by_event[name] += duration
            self.traces += name == self.EVENTS[0]
            self.compiles += name == self.EVENTS[2]


@dataclasses.dataclass
class RunRecord:
    """What a per-layer reader gets: the window's round logs, the program's
    spans recorded in it, the trace summary, the cell and the chip's peaks."""

    config: dict
    traffic: dict
    window_s: float
    logs: list
    spans: list[dict]
    trace: object | None
    peak: dict | None
    chips: int = 1


class Window:
    """The ``on_round`` seam: opens the window after the warm-up rounds,
    closes it at the end of the first round that ends after ``seconds`` (and
    no earlier than the last followed round), and keeps the parameters after
    rounds 1 and ``FOLLOWED_ROUNDS`` on the host (``kept``, flat dicts), so
    that the program has the device to itself.  Round 1's copy is taken
    before the window opens; round ``FOLLOWED_ROUNDS``'s is started
    asynchronously in the window and collected at the next round or at the
    close."""

    def __init__(self, cfg, seconds: float, clock: CompileClock,
                 trace: bool) -> None:
        self.cfg, self.seconds, self.clock, self.trace = (cfg, seconds,
                                                          clock, trace)
        self.kept: dict[int, dict] = {}
        self.copying = None                # (t, device tree) on its way
        self.first = None                  # first round of the window
        self.t0 = self.t1 = None
        self.compile_s0 = 0.0
        self.counts0 = (0, 0)
        self.dispatches = 0
        self.rec = None
        self.trace_dir = None
        self.tracing = False
        self.trace_window = (0.0, 0.0)     # obs clock
        self.stop_trace_s = 0.0            # spent inside the window

    def on_round(self, t: int, params) -> None:
        import jax

        from bench import reference

        self._collect()
        if t == 0:
            self.kept[t] = reference.flat(params)
        elif t == FOLLOWED_ROUNDS - 1:
            for leaf in jax.tree_util.tree_leaves(params):
                leaf.copy_to_host_async()
            self.copying = (t, params)
        if self.t1 is not None:
            return
        if self.t0 is None:
            if t >= WARMUP_ROUNDS - 1:
                self._open(t, params)
            return
        if self.tracing and time.perf_counter() - self.t0 >= TRACE_SECONDS:
            self._stop_trace(params)
        if (t >= FOLLOWED_ROUNDS - 1
                and time.perf_counter() - self.t0 >= self.seconds):
            self._close(params)

    def _collect(self) -> None:
        """Finish the host copy under way and drop its device tree."""
        from bench import reference

        if self.copying is not None:
            t, params = self.copying
            self.kept[t] = reference.flat(params)
            self.copying = None

    def _open(self, t: int, params) -> None:
        import jax

        import repro.obs as obs
        from repro.instrument import jit_dispatches

        jax.block_until_ready(params)
        self.first = t + 1
        self.compile_s0 = self.clock.seconds
        self.counts0 = (self.clock.traces, self.clock.compiles)
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            # annotations and device ops only: no Python function tracing
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=options)
            self.tracing = True
            self.rec = obs.enable(jax_profiler=True)
            self.trace_window = (self.rec.now(), 0.0)
        self.dispatches = jit_dispatches()
        self.t0 = time.perf_counter()

    def _close(self, params) -> None:
        import jax

        import repro.obs as obs
        from repro.instrument import jit_dispatches

        jax.block_until_ready(params)
        self.t1 = time.perf_counter()
        self.dispatches = jit_dispatches() - self.dispatches
        if self.tracing:
            self._stop_trace(params)
        self._collect()
        if self.trace:
            obs.disable()
        # the program's own stop: should_stop() reads the budget next
        self.cfg.epsilon_budget = 0.0

    def _stop_trace(self, params) -> None:
        import jax

        jax.block_until_ready(params)
        self.trace_window = (self.trace_window[0], self.rec.now())
        t = time.perf_counter()
        jax.profiler.stop_trace()
        self.tracing = False
        self.stop_trace_s = time.perf_counter() - t


def build_program(cell: Cell, raw, seed: int):
    """(model, hospitals, ArmConfig) for the program, from the raw arrays:
    initial weights from ``key(seed)``, the stream from ``STREAM_SEED``."""
    import jax

    import repro.arms as arms
    from repro.core.dp import DPConfig

    conf, dp = cell.config, cell.config["dp"]
    model, silos = load_kind(conf).program(conf, cell.traffic, raw)
    init = model.init_fn
    model = dataclasses.replace(
        model, init_fn=lambda _key: init(jax.random.key(seed)))
    cfg = arms.ArmConfig(
        rounds=MAX_ROUNDS, batch_size=cell.traffic["batch_size"],
        lr=conf["lr"], seed=STREAM_SEED, use_secagg=cell.traffic["secagg"],
        secagg_frac_bits=conf["secagg_frac_bits"],
        dp=DPConfig(clip_norm=dp["clip_norm"],
                    noise_multiplier=dp["noise_multiplier"],
                    delta=dp["delta"], microbatch_size=dp["microbatch_size"]),
    )
    return model, silos, cfg


def drive(cell: Cell, raw, seed: int, seconds: float, clock: CompileClock,
          trace: bool = False):
    """Run the program from the seed through the window: (report, window)."""
    import repro.arms as arms

    model, silos, cfg = build_program(cell, raw, seed)
    window = Window(cfg, seconds, clock, trace)
    report = arms.run("decaph", model, silos, cfg, backend="ideal",
                      on_round=window.on_round)
    if window.t1 is None:
        raise RuntimeError(f"the run ended after {report.rounds_completed} "
                           "rounds, before the window closed")
    return report, window


def program_outputs(report, window) -> dict:
    """What the comparison reads from the program's run."""
    return {
        "losses": [log.loss for log in report.logs[:FOLLOWED_ROUNDS]],
        "p1": window.kept[0],
        "p3": window.kept[FOLLOWED_ROUNDS - 1],
        "epsilon": float(report.epsilon),
        "stepped": sum(log.aggregate_batch > 0 for log in report.logs),
    }


def _reference(cell: Cell, raw, seed: int, dtype):
    from bench import reference

    model = load_kind(cell.config).reference(cell.config, cell.traffic)
    return reference.run(model, raw, cell.config, cell.traffic, seed,
                         STREAM_SEED, FOLLOWED_ROUNDS, dtype)


def check(cell: Cell, raw, seed: int, prog: dict):
    """Readings of ``prog`` against the reference's first rounds."""
    import jax.numpy as jnp

    from bench import accountant, compare

    ref = _reference(cell, raw, seed, jnp.float32)
    dp = cell.config["dp"]
    eps_ref = accountant.epsilon(ref.rate, dp["noise_multiplier"],
                                 prog["stepped"], dp["delta"],
                                 cell.config["accountant_orders"])
    return compare.readings(prog, ref, eps_ref, cell.config["lr"])


def control_outputs(cell: Cell, raw, seed: int) -> dict:
    """The reference in bfloat16, in the program's place (the control)."""
    import jax.numpy as jnp

    from bench import accountant

    ctl = _reference(cell, raw, seed, jnp.bfloat16)
    dp = cell.config["dp"]
    return {
        "losses": ctl.losses, "p1": ctl.params[1],
        "p3": ctl.params[FOLLOWED_ROUNDS],
        "epsilon": accountant.epsilon(ctl.rate, dp["noise_multiplier"],
                                      FOLLOWED_ROUNDS, dp["delta"],
                                      cell.config["accountant_orders"],
                                      np.float32),
        "stepped": FOLLOWED_ROUNDS,
    }


def enable_cache() -> str:
    """Serve every program from JAX's persistent cache (the program's own
    directory choice); returns the directory."""
    import jax

    from repro.compile_cache import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


def device_info() -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count()}


def _memory_peak() -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _live_bytes() -> int:
    """Bytes of the arrays still alive on the devices."""
    import jax

    return sum(a.nbytes for a in jax.live_arrays())


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float) -> dict:
    """One run of ``cell``: the result object of the benchmark's last line.
    ``t_start`` is the process's start on the ``perf_counter`` clock."""
    from bench import cohort, compare, flops, trace_reduce

    clock = CompileClock()
    device = device_info()
    t_cohort = time.perf_counter()
    raw = cohort.cohort(cell.config, cell.traffic, seed)
    t_cohort = time.perf_counter() - t_cohort
    report, window = drive(cell, raw, seed, seconds, clock, trace)
    setup_s = window.t0 - t_start - t_cohort
    window_s = window.t1 - window.t0 - window.stop_trace_s
    logs = [log for log in report.logs if log.round >= window.first]
    stepped = [log for log in logs if log.aggregate_batch > 0]
    compile_setup = window.compile_s0
    print(f"set-up {setup_s!r} s = compile {compile_setup!r} s + other "
          f"{setup_s - compile_setup!r} s; compile by stage "
          f"{ {k.rsplit('/', 1)[1]: v for k, v in clock.by_event.items()} }; "
          f"compile cache {clock.cache}; cohort {t_cohort!r} s, not in "
          "set-up", flush=True)
    print(f"window: {len(logs)} rounds in {window_s!r} s; traces "
          f"{clock.traces - window.counts0[0]}, compiles "
          f"{clock.compiles - window.counts0[1]} inside it; "
          f"{window.dispatches / max(len(logs), 1)!r} jit dispatches/round",
          flush=True)
    device["memory_peak_bytes"] = _memory_peak()

    result = {"correct": False, "attempted": len(logs),
              "failed": len(logs) - len(stepped), "device": device}
    if trace:
        spans = [ev for ev in window.rec.events() if ev["type"] == "span"]
        summary = trace_reduce.reduce(
            trace_reduce.find_xplane(window.trace_dir), spans,
            window.trace_window)
        shutil.rmtree(window.trace_dir, ignore_errors=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        run = RunRecord(cell.config, cell.traffic, window_s, logs, spans,
                        summary, flops.peak(device["kind"]), cell.chips)
        metrics = {}
        for m in cell.per_layer:
            value = load_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": [list(x) for x in summary.top_ops],
            "idle_gaps": [list(x) for x in summary.gaps]}
    else:
        metrics = {"round_ms": {"value": 1e3 * window_s / len(stepped),
                                "unit": "ms"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
    result["metrics"] = metrics

    prog = program_outputs(report, window)
    del report, window
    gc.collect()
    print(f"device bytes live before the reference: {_live_bytes()}",
          flush=True)
    t_ref = time.perf_counter()
    values = check(cell, raw, seed, prog)
    print(f"reference: {time.perf_counter() - t_ref!r} s", flush=True)
    result["correct"] = compare.judge(values, cell.limits)
    result["compared"] = {k: {"value": values[k], "limit": cell.limits[k]}
                          for k in cell.limits}
    for k in cell.limits:
        print(f"{k}: {values[k]!r} (limit {cell.limits[k]!r})",
              file=sys.stderr, flush=True)
    return result
