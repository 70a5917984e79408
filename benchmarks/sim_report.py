"""Systems report: every registered arm on a 5-hospital heterogeneous trace.

For each arm in the registry (decaph, fl, primia, local, gossip, gossip-dp)
the sim backend reports simulated wall-clock, bytes-on-wire, rounds
completed, epsilon and final utility — answering the deployment questions
(stragglers, flaky networks, dropout) the idealized backend cannot.  The
table enumerates ``repro.arms.names()``, so a newly registered arm shows up
here without touching this file.

Also certifies the dropout-recovery acceptance property end to end: a
hospital dropping mid-round on the decaph arm completes via Shamir mask
recovery, and the recovered aggregate equals the plain sum of the surviving
participants' contributions within fixed-point tolerance (raises otherwise).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.arms as arms
from repro.compile_cache import enable_compilation_cache
from repro.core.dp import DPConfig
from repro.core.secagg import DropoutRobustSession, SecAggConfig
from repro.data.synthetic import make_gemini_like
from repro.models.tabular import linear_model, pooled_accuracy
from repro.scenarios.presets import FIVE_HOSPITAL_TRACE
from repro.sim import Topology, nodes_from_trace

# The canonical 5-hospital heterogeneous cohort — defined exactly once, in
# the scenario preset library (shared with examples/ and the sweep presets).
SCENARIO = FIVE_HOSPITAL_TRACE


def _topology_for(arm_cls, n: int, center: int) -> Topology:
    """The arm's natural topology, carrying the scenario's link model."""
    default = SCENARIO["topology"]["default"]
    if arm_cls.topology_kind == "star":
        spec = {"kind": "star", "center": center, "default": default}
    elif arm_cls.topology_kind == "ring":
        spec = {"kind": "ring", "default": default}
    else:
        spec = dict(SCENARIO["topology"])  # full mesh incl. slow-WAN links
    spec.setdefault("n", n)
    return Topology.from_trace(spec)


def certify_dropout_recovery(
    n: int = 5, dim: int = 64, seed: int = 3
) -> float:
    """Acceptance property at the protocol level: recovered == survivor sum."""
    rng = np.random.default_rng(seed)
    vals = [jnp.asarray(rng.normal(0, 2, dim).astype(np.float32))
            for _ in range(n)]
    cfg = SecAggConfig(n, frac_bits=16, seed=seed)
    session = DropoutRobustSession(cfg, vals[0], threshold=3)
    dropped = {1, 3}
    uploads = {i: session.upload(i, vals[i])
               for i in range(n) if i not in dropped}
    out = np.asarray(session.aggregate(uploads))
    expected = np.sum([np.asarray(vals[i]) for i in range(n)
                       if i not in dropped], axis=0)
    err = float(np.abs(out - expected).max())
    tol = n * 2.0 ** -(cfg.frac_bits - 1)
    if err > tol:
        raise AssertionError(
            f"Shamir recovery off by {err} (> fixed-point tolerance {tol})"
        )
    return err


def run(fast: bool = True) -> list[dict]:
    n_features = 32 if fast else 436
    rounds = 12 if fast else 60
    silos = arms.normalize_participants(
        make_gemini_like(seed=0, n_total=1200 if fast else 5000,
                         n_silos=5, n_features=n_features)
    )
    model = linear_model(n_features)
    cfg = arms.ArmConfig(
        rounds=rounds, batch_size=64, lr=0.4, seed=0,
        dp=DPConfig(clip_norm=1.0, noise_multiplier=0.8, microbatch_size=8),
    )

    rows = []
    err = certify_dropout_recovery()
    rows.append({
        "name": "sim_dropout_recovery_certified",
        "us_per_call": 0.0,
        "derived": f"max_abs_err={err:.2e};survivors=3of5;threshold=3",
    })

    for arm in arms.names():
        arm_cls = arms.get(arm)
        nodes = nodes_from_trace(SCENARIO["nodes"])
        topo = _topology_for(arm_cls, len(nodes), cfg.fl_server)
        t0 = time.time()
        rep = arms.run(arm, model, silos, cfg, backend="sim",
                       nodes=nodes, topo=topo)
        elapsed_us = (time.time() - t0) * 1e6
        # rep.params is the arm's headline model (node arms pick it in
        # consensus(): local -> node 0, gossip -> the average)
        acc = pooled_accuracy(model, rep.params, silos)
        rows.append({
            "name": f"sim_{arm}",
            "us_per_call": elapsed_us,
            "derived": (
                f"sim_wall_clock_s={rep.wall_clock:.3f};"
                f"bytes_on_wire={rep.bytes_on_wire:.0f};"
                f"rounds={rep.rounds_completed};"
                f"epsilon={rep.epsilon:.2f};"
                f"accuracy={acc:.3f};"
                f"dropouts={rep.dropout_events};"
                f"recoveries={rep.recoveries};"
                f"events={rep.events}"
            ),
        })
        if arm == "decaph" and rep.recoveries < 1:
            raise AssertionError(
                "scenario injects a mid-run dropout but decaph performed "
                "no Shamir recovery — dropout did not land mid-round"
            )
    return rows


if __name__ == "__main__":
    enable_compilation_cache()
    header = (f"{'arm':<10} {'sim wall (s)':>12} {'bytes on wire':>14} "
              f"{'rounds':>6} {'epsilon':>8} {'accuracy':>8} {'recov':>5}")
    rows = run(fast=True)
    print(header)
    print("-" * len(header))
    for r in rows:
        d = dict(kv.split("=") for kv in r["derived"].split(";"))
        if r["name"] == "sim_dropout_recovery_certified":
            print(f"dropout recovery certified: max_abs_err={d['max_abs_err']}"
                  f" ({d['survivors']} survivors, threshold={d['threshold']})")
            continue
        print(f"{r['name'][4:]:<10} {float(d['sim_wall_clock_s']):>12.3f} "
              f"{float(d['bytes_on_wire']):>14.0f} {d['rounds']:>6} "
              f"{float(d['epsilon']):>8.2f} {float(d['accuracy']):>8.3f} "
              f"{d.get('recoveries', '0'):>5}")
