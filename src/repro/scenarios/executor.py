"""Execute scenario specs: one cell, or a cached, process-parallel sweep.

``run_spec`` materialises a ``ScenarioSpec`` (cohort, model, nodes, topology,
arm config), runs it through ``repro.arms.run`` and returns a plain-JSON
metrics dict.  ``run_sweep`` drives a list of specs through the result cache:
hits are served from disk, misses execute — inline for ``jobs=1``, else on a
spawn-context process pool (JAX initialised in this process must not be
forked) — and every fresh result is persisted, making sweeps resumable.
The pool exists only on the CPU: an accelerator belongs to one process at a
time, and this one holds it, so elsewhere every cell runs inline.

JAX-heavy imports happen inside functions: a fully-cached sweep never
builds models, data or backends (it still pays the one arm-registry import
that sweep-axis expansion needs — see ``grid._registered_arms``).

Pool workers turn on the persistent compilation cache
(``repro.compile_cache``) in their initializer, so cell N's compile is cell
N+1's disk hit, across processes and across invocations.
"""

from __future__ import annotations

import dataclasses
import logging
import math
import sys
import time
from concurrent.futures import ProcessPoolExecutor
from typing import Callable, Sequence

import repro.obs as obs
from repro.compile_cache import enable_compilation_cache
from repro.scenarios import presets as presets_lib
from repro.scenarios.cache import ResultCache, device_identity
from repro.scenarios.spec import ScenarioSpec

logger = logging.getLogger(__name__)

def build_scenario(spec: ScenarioSpec):
    """(model, silos, cfg, nodes, topo) — everything ``repro.arms.run`` needs.

    ``nodes``/``topo`` are None for the idealized backend.
    """
    import repro.arms as arms
    from repro.arms import backends as backends_lib
    from repro.core.dp import DPConfig
    from repro.sim import Topology, nodes_from_trace

    arm_cls = arms.get(spec.arm)  # validates the arm name early
    backend_info = backends_lib.get_backend(spec.backend).info
    model = presets_lib.build_model(spec)
    silos = presets_lib.build_silos(spec)
    if presets_lib.normalizes(spec.task):
        silos = arms.normalize_participants(silos)
    cfg = arms.ArmConfig(
        rounds=spec.rounds, batch_size=spec.batch_size, lr=spec.lr,
        seed=spec.seed, use_secagg=spec.use_secagg,
        fl_local_steps=spec.fl_local_steps, fedprox_mu=spec.fedprox_mu,
        epsilon_budget=spec.epsilon_budget,
        participation_rate=spec.participation_rate,
        clipping=spec.clipping,
        dp=DPConfig(clip_norm=spec.clip_norm,
                    noise_multiplier=spec.noise_multiplier,
                    microbatch_size=spec.microbatch_size),
    )
    if not backend_info.supports_sim_time:
        return model, silos, cfg, None, None
    if spec.population is not None:
        # distributional cell: materialise the node/topology traces from the
        # population description (deterministic in spec.seed)
        from repro.population.spec import PopulationSpec

        pop = PopulationSpec.from_dict(
            {"hospitals": spec.hospitals, "seed": spec.seed,
             **spec.population}
        )
        return (model, silos, cfg, nodes_from_trace(pop.build_nodes()),
                Topology.from_trace(pop.build_topology()))
    nodes = nodes_from_trace(presets_lib.default_nodes(spec))
    if spec.topology is not None:
        topo_spec = dict(spec.topology)
        topo_spec.setdefault("n", spec.hospitals)
        topo = Topology.from_trace(topo_spec)
    else:
        kind = arm_cls.topology_kind
        spec_kind = {"kind": kind, "n": spec.hospitals,
                     "default": {"bandwidth": spec.bandwidth,
                                 "latency": spec.latency}}
        if kind == "star":
            spec_kind["center"] = cfg.fl_server
        topo = Topology.from_trace(spec_kind)
    return model, silos, cfg, nodes, topo


def run_spec(spec: ScenarioSpec) -> dict:
    """Execute one cell and return its plain-JSON metrics."""
    return run_spec_report(spec)[0]


def run_spec_report(spec: ScenarioSpec):
    """Execute one cell: (its plain-JSON metrics, the arm's ``RunReport``),
    for callers that check the trained params as well as the row."""
    import jax
    import numpy as np

    import repro.arms as arms

    model, silos, cfg, nodes, topo = build_scenario(spec)
    rec = obs.recorder()
    spans_before = rec.span_totals() if rec is not None else None
    t0 = time.time()
    with obs.span("sweep.cell", cat="sweep", cell=spec.name, arm=spec.arm,
                  backend=spec.backend, hospitals=spec.hospitals):
        rep = arms.run(spec.arm, model, silos, cfg, backend=spec.backend,
                       nodes=nodes, topo=topo)
    host_seconds = time.time() - t0
    # rep.params is always the arm's headline model: node arms pick it in
    # consensus() (local -> node 0, gossip -> the average)
    headline = rep.params
    n_params = int(sum(np.prod(np.shape(leaf)) or 1
                       for leaf in jax.tree_util.tree_leaves(headline)))
    row = {
        "name": spec.name,
        "key": spec.spec_hash(),
        "task": spec.task,
        "arm": spec.arm,
        "backend": spec.backend,
        "hospitals": spec.hospitals,
        "model_size": spec.model_size,
        "model_params": n_params,
        "rounds_completed": rep.rounds_completed,
        "epsilon": float(rep.epsilon),
        # None (JSON null), not NaN: NaN breaks strict JSON consumers and
        # NaN != NaN would make cached results compare unequal to fresh ones
        "mean_loss": (float(rep.mean_loss())
                      if math.isfinite(rep.mean_loss()) else None),
        "accuracy": presets_lib.pooled_metric(spec, model, headline, silos),
        "wall_clock": float(rep.wall_clock),
        "bytes_on_wire": float(rep.bytes_on_wire),
        "dropout_events": int(rep.dropout_events),
        "recoveries": int(rep.recoveries),
        "lost_rounds": int(rep.lost_rounds),
        "events": int(rep.events),
        "noise_topups": int(rep.noise_topups),
        "host_seconds": host_seconds,
        "device": device_identity(),
    }
    if spans_before is not None:
        # per-cell host-time phase breakdown (fused dispatch vs aggregate vs
        # transport ...) — the delta of the recorder's span totals across
        # this cell, surfaced in the BENCH row only when recording is on
        after = rec.span_totals()
        row["phase_seconds"] = {
            name: round(total - (spans_before.get(name) or (0, 0.0))[1], 6)
            for name, (_, total) in sorted(after.items())
            if total - (spans_before.get(name) or (0, 0.0))[1] > 0
            and name != "sweep.cell"
        }
    return row, rep


def _pool_cell(spec_dict: dict) -> dict:
    """Top-level pool target (must be picklable under spawn)."""
    return run_spec(ScenarioSpec.from_dict(spec_dict))


@dataclasses.dataclass
class SweepOutcome:
    """What a sweep invocation did: the results plus cache bookkeeping."""

    results: list[dict]
    hits: int
    misses: int
    elapsed: float

    @property
    def cells(self) -> int:
        return len(self.results)


def run_sweep(
    specs: Sequence[ScenarioSpec],
    cache: ResultCache,
    *,
    jobs: int = 1,
    force: bool = False,
    runner: Callable[[ScenarioSpec], dict] | None = None,
    progress: Callable[[str], None] | None = None,
) -> SweepOutcome:
    """Run every spec through the cache; execute only the misses.

    ``runner`` overrides cell execution (tests inject a counting fake; the
    process pool is bypassed whenever a runner is given or ``jobs <= 1``).
    Off the CPU the pool is bypassed too, and the sweep says so on stderr:
    a child process cannot use the accelerator this process holds.
    """
    t0 = time.time()
    say = progress or (lambda msg: None)
    results: list[dict | None] = [None] * len(specs)
    pending: list[int] = []
    hits = 0
    for idx, spec in enumerate(specs):
        cached = None if force else cache.get(spec)
        if cached is not None:
            # relabel on serve: names are excluded from the cache key, so a
            # renamed sweep/cell must not surface its original label
            results[idx] = {**cached, "name": spec.name}
            hits += 1
        else:
            pending.append(idx)
    say(f"{len(specs)} cells: {hits} cached, {len(pending)} to run")

    if pending and runner is None and jobs > 1 and len(pending) > 1:
        platform = cache.device["platform"]
        if platform != "cpu":
            print(f"run_sweep: running {len(pending)} cells inline, not on "
                  f"{jobs} workers: the {platform} device belongs to this "
                  "process", file=sys.stderr)
            jobs = 1

    if pending:
        if runner is None and jobs > 1 and len(pending) > 1:
            # spawn, not fork: this process has (or will have) a live JAX
            # runtime, whose threads do not survive forking.  Every finished
            # cell is cached as it completes, so one failing cell costs only
            # itself — the re-run resumes from everything that succeeded.
            import multiprocessing as mp
            from concurrent.futures import as_completed

            ctx = mp.get_context("spawn")
            first_error: BaseException | None = None
            with ProcessPoolExecutor(max_workers=min(jobs, len(pending)),
                                     mp_context=ctx,
                                     initializer=enable_compilation_cache
                                     ) as pool:
                futures = {
                    pool.submit(_pool_cell, specs[i].to_dict()): i
                    for i in pending
                }
                for fut in as_completed(futures):
                    idx = futures[fut]
                    try:
                        results[idx] = fut.result()
                    except BaseException as e:  # noqa: BLE001 - re-raised
                        say(f"FAILED {specs[idx].name}: {e}")
                        first_error = first_error or e
                        continue
                    cache.put(specs[idx], results[idx])
                    say(f"ran  {specs[idx].name}")
            if first_error is not None:
                raise first_error
        else:
            run_one = runner or run_spec
            for idx in pending:
                results[idx] = run_one(specs[idx])
                cache.put(specs[idx], results[idx])
                say(f"ran  {specs[idx].name}")

    return SweepOutcome(
        results=[r for r in results if r is not None],
        hits=hits, misses=len(pending), elapsed=time.time() - t0,
    )
