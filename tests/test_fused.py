"""The fused cohort round-step: loop-equivalence, dispatch counts, secagg.

The fused path vmaps the per-participant numerics, which re-associates
float math at the ulp level — so fused-vs-loop agreement is tested to a
tight-but-nonzero tolerance, while the *cross-backend* bit-exactness of the
fused path itself is covered by ``tests/test_arms_equivalence.py`` (both
backends run the same fused program).
"""

import contextlib
import dataclasses

import jax
import numpy as np
import pytest

import repro.arms as arms
from repro.arms import fused
from repro.core.dp import DPConfig

from test_arms_equivalence import _cfg, _make_model, _silos

ROUND_ARMS = ["decaph", "fl", "fedprox", "scaffold", "primia"]
FUSED_ARMS = ["decaph", "fl", "fedprox", "scaffold", "primia"]


def _run(arm, cfg):
    return arms.run(arm, _make_model(5), _silos(), cfg)


def _leaves_close(a, b, atol):
    import jax
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=0.0, atol=atol)


@pytest.mark.parametrize("arm_name", ROUND_ARMS)
def test_fused_matches_loop_path(arm_name):
    """Same draws, same schedule, same trajectory (to vmap re-association)."""
    cfg = _cfg(rounds=5)
    fused_rep = _run(arm_name, cfg)
    loop_rep = _run(arm_name, dataclasses.replace(cfg, fused_rounds=False))
    assert fused_rep.rounds_completed == loop_rep.rounds_completed
    _leaves_close(fused_rep.params, loop_rep.params, atol=1e-5)
    for a, b in zip(fused_rep.logs, loop_rep.logs):
        assert a.round == b.round and a.leader == b.leader
        assert a.aggregate_batch == b.aggregate_batch
        if np.isfinite(a.loss) or np.isfinite(b.loss):
            assert abs(a.loss - b.loss) < 1e-5
    assert fused_rep.epsilon == pytest.approx(loop_rep.epsilon, abs=1e-12)


@pytest.mark.parametrize("local_steps", [1, 3])
def test_fused_fl_fedavg_matches_loop(local_steps):
    cfg = _cfg(rounds=4, fl_local_steps=local_steps)
    fused_rep = _run("fl", cfg)
    loop_rep = _run("fl", dataclasses.replace(cfg, fused_rounds=False))
    _leaves_close(fused_rep.params, loop_rep.params, atol=1e-5)


def test_fused_primia_ragged_retirement_matches_loop():
    """primia's fused round pads the ragged per-client Poisson draws (each
    client has its own rate AND pad) to the cohort max, and keeps matching
    the loop path bit-for-bit on the accountants even as small clients
    exhaust their local budgets and the active cohort shrinks."""
    model = _make_model(5)
    # unequal silos: the small clients' higher sampling rates exhaust their
    # local budgets first (the legacy-shim retirement setup)
    silos = _silos(seed=3, sizes=(300, 60, 60))
    cfg = _cfg(rounds=20, epsilon_budget=2.0,
               dp=DPConfig(clip_norm=1.0, noise_multiplier=1.0,
                           microbatch_size=8))
    fused_rep = arms.run("primia", model, silos, cfg)
    loop_rep = arms.run("primia", model, silos,
                        dataclasses.replace(cfg, fused_rounds=False))
    assert fused_rep.rounds_completed == loop_rep.rounds_completed
    assert fused_rep.rounds_completed < 20  # retirement actually happened
    _leaves_close(fused_rep.params, loop_rep.params, atol=1e-5)
    assert fused_rep.epsilon == pytest.approx(loop_rep.epsilon, abs=1e-12)


def test_fused_decaph_secagg_matches_loop():
    """Under SecAgg the payloads differ at the ulp before encoding, so the
    field sums agree to one quantisation step per participant."""
    cfg = _cfg(rounds=4, use_secagg=True)
    fused_rep = _run("decaph", cfg)
    loop_rep = _run("decaph", dataclasses.replace(cfg, fused_rounds=False))
    _leaves_close(fused_rep.params, loop_rep.params, atol=1e-3)


@pytest.mark.parametrize("arm_name", FUSED_ARMS)
def test_fused_round_is_one_dispatch(arm_name):
    """The O(1)-dispatch contract: one cohort program launch per round."""
    cfg = _cfg(rounds=3)
    _run(arm_name, cfg)  # compile warmup for this config shape
    fused.reset_jit_dispatches()
    rep = _run(arm_name, cfg)
    assert rep.rounds_completed == 3
    assert fused.jit_dispatches() == 3  # exactly one per round
    fused.reset_jit_dispatches()
    loop = _run(arm_name, dataclasses.replace(cfg, fused_rounds=False))
    assert fused.jit_dispatches() >= loop.rounds_completed * 4  # O(H)


def test_fused_round_withheld_payloads_never_hit_the_wire():
    """With SecAgg off on the idealized backend, payloads stay on device:
    the per-participant Contribution carries None and the aggregate is
    served from the in-jit reduced sum."""
    captured = {}

    class Probe(arms.get("decaph")):
        def aggregate(self, params, contributions, services):
            captured["payloads"] = [c.payload for c in contributions.values()]
            return super().aggregate(params, contributions, services)

    cfg = _cfg(rounds=2)
    model, silos = _make_model(5), _silos()
    rep = arms.LocalRunner().run(Probe(model, silos, cfg))
    assert rep.rounds_completed == 2
    assert all(p is None for p in captured["payloads"])


def test_sim_backend_gets_real_payloads():
    """The sim backend ships each contribution over the wire, so the fused
    path must hand it real per-participant payload trees."""
    from repro.sim import Link, Topology, nodes_from_trace

    cfg = _cfg(rounds=2)
    model, silos = _make_model(5), _silos()
    rep = arms.run(
        "decaph", model, silos, cfg, backend="sim",
        nodes=nodes_from_trace([{"throughput": 1000.0, "overhead": 0.01}] * 4),
        topo=Topology.full(4, Link(bandwidth=1e15, latency=0.0)),
    )
    assert rep.rounds_completed == 2


def test_stack_poisson_consumes_rng_like_the_loop():
    """Identical draws in identical order — the fused-path contract."""
    from repro.arms.base import poisson_batch

    silos = _silos()
    rng_a = np.random.default_rng(7)
    rng_b = np.random.default_rng(7)
    cb = fused.stack_poisson(rng_a, silos, [0, 1, 2, 3], 0.1, 32, steps=2)
    for s, i in enumerate([0, 1, 2, 3]):
        for k in range(2):
            b, m, n = poisson_batch(rng_b, silos[i], 0.1, 32)
            np.testing.assert_array_equal(cb.x[s, k], b["x"])
            np.testing.assert_array_equal(cb.masks[s, k], m)
            assert cb.counts[s, k] == n
    assert cb.sizes == [int(r.sum()) for r in cb.counts]


def test_stack_poisson_grows_pad_for_the_whole_cohort():
    """One oversized draw re-pads the round; masks keep the pad inert."""
    silos = _silos()
    rng = np.random.default_rng(0)
    cb = fused.stack_poisson(rng, silos, [0, 1], 0.9, 8)  # rate 0.9 >> pad 8
    assert cb.x.shape[1] >= 64  # grown to a power of two that fits
    assert (cb.masks.sum(axis=1) == np.asarray(cb.sizes)).all()


class _PlainJitExecutor:
    """An executor that runs each program as plain jit on one device: it
    takes ``DeCaPHArm.fused_round`` down its mesh branch, which feeds the
    program the batch stacked on the host."""

    def round_pad(self, pad):
        return pad

    def mark(self, arr, axis):
        pass

    def execute(self, wrapper, args, kwargs):
        return wrapper.jitted(*args, **kwargs)


def _decaph(**kw):
    return arms.get("decaph")(_make_model(5), _silos(), _cfg(**kw))


def _decaph_round(arm, seed, *, host_stack, need_reduced):
    rng = np.random.default_rng(seed)
    ctx = (fused.execution_context(_PlainJitExecutor()) if host_stack
           else contextlib.nullcontext())
    with ctx:
        contribs, out = arm.fused_round(
            arm.init_params(), [0, 1, 2, 3], 0, rng, 4, need_payloads=True,
            need_reduced=need_reduced)
    return contribs, out, rng


@pytest.mark.parametrize("max_pad", [None, 4], ids=["fits", "outgrows"])
@pytest.mark.parametrize("secagg", [True, False],
                         ids=["secagg", "nosecagg"])
def test_device_gather_round_bit_identical_to_host_stack(secagg, max_pad):
    """The batch gathered in the program from the rows on the device gives
    the host-stacked round's payloads, reduced sum, losses and sizes bit
    for bit; with a pad of 4 every draw outgrows it (the retrace)."""
    arm = _decaph(use_secagg=secagg, max_pad_batch=max_pad)
    dev, dev_out, _ = _decaph_round(arm, 5, host_stack=False,
                                    need_reduced=not secagg)
    host, host_out, _ = _decaph_round(arm, 5, host_stack=True,
                                      need_reduced=not secagg)
    if max_pad is not None:
        assert max(c.size for c in dev.values()) > max_pad
    assert sorted(dev) == sorted(host) == [0, 1, 2, 3]
    for i in dev:
        assert dev[i].size == host[i].size
        assert dev[i].loss == host[i].loss
        for a, b in zip(*map(jax.tree_util.tree_leaves,
                             (dev[i].payload, host[i].payload))):
            np.testing.assert_array_equal(a, b)
    # SecAgg on: the payload stack; off: the in-jit reduced sum
    for a, b in zip(*map(jax.tree_util.tree_leaves, (dev_out, host_out))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("max_pad", [None, 4], ids=["fits", "outgrows"])
def test_device_gather_round_consumes_rng_like_the_loop(max_pad):
    from repro.arms.base import poisson_batch

    arm = _decaph(max_pad_batch=max_pad)
    _, _, rng = _decaph_round(arm, 9, host_stack=False, need_reduced=True)
    loop_rng = np.random.default_rng(9)
    for part in arm.participants:
        poisson_batch(loop_rng, part, arm.rate, arm.pad)
    assert rng.bit_generator.state == loop_rng.bit_generator.state


@pytest.mark.parametrize("steps", [None, 2])
def test_cohort_batch_stacks_lazily_like_the_loop(steps):
    """Without ``host_stack`` nothing is stacked until ``x``/``y`` are read;
    then they hold the loop's batches, zero-padded to the cohort's pad."""
    from repro.arms.base import poisson_batch

    silos = _silos()
    rng_b = np.random.default_rng(4)
    cb = fused.stack_poisson(np.random.default_rng(4), silos, [1, 3], 0.1,
                             4, steps=steps, host_stack=False)
    assert cb.stacked == {}
    pad = cb.rows.shape[-1]
    for s, i in enumerate([1, 3]):
        for k in range(steps or 1):
            b, m, n = poisson_batch(rng_b, silos[i], 0.1, 4)
            at = (s,) if steps is None else (s, k)
            for leaf in ("x", "y"):
                want = np.zeros((pad,) + b[leaf].shape[1:], b[leaf].dtype)
                want[:len(b[leaf])] = b[leaf]
                np.testing.assert_array_equal(getattr(cb, leaf)[at], want)
            np.testing.assert_array_equal(cb.masks[at][:len(m)], m)
            assert cb.counts[at] == n
    assert set(cb.stacked) == {"x", "y"} and cb.x is cb.x


@pytest.mark.parametrize("arm_name", ["decaph", "fl"])
def test_stack_poisson_span_counts_the_host_bytes(arm_name):
    """``host_bytes`` is 0 where the program gathers the batch on the
    device (DeCaPH), and the stacked examples where the host stacks it."""
    import repro.obs as obs

    with obs.recording() as rec:
        rep = _run(arm_name, _cfg(rounds=2))
    spans = [e for e in rec.events() if e["type"] == "span"
             and e["name"] == "host_rng.stack_poisson"]
    assert len(spans) == rep.rounds_completed == 2
    for e in spans:
        args = e["args"]
        assert set(args) == {"cohort", "pad", "host_bytes"}
        assert args["cohort"] == 4
        # x: 5 float32 features, y: one float32 label, per slot
        stacked = args["cohort"] * args["pad"] * (5 + 1) * 4
        assert args["host_bytes"] == (0 if arm_name == "decaph" else stacked)


def test_device_rows_are_arguments_of_the_cohort_program():
    """The rows on the device enter the gathered cohort program as
    arguments: no constant of their shape is baked into it."""
    arm = _decaph()
    _decaph_round(arm, 0, host_stack=False, need_reduced=True)
    x_all, y_all = arm._rows
    assert x_all.shape == (sum(len(p) for p in arm.participants) + 1, 5)
    cb = fused.stack_poisson(np.random.default_rng(0), arm.participants,
                             [0, 1, 2, 3], arm.rate, arm.pad,
                             host_stack=False)
    text = arm._gather_step.jitted.lower(
        arm.init_params(), x_all, y_all, cb.rows, cb.masks, np.int32(17),
        np.arange(4, dtype=np.int32), 4).as_text()
    shape = "tensor<{}x{}xf32>".format(*x_all.shape)
    assert shape in text
    assert [ln for ln in text.splitlines()
            if "constant" in ln and shape in ln] == []


def test_scaffold_beats_fedavg_under_heterogeneity():
    """The control variates must actually correct client drift: on skewed
    silos SCAFFOLD's final loss should not be worse than plain FedAvg's."""
    model = _make_model(5)
    silos = _silos(seed=3, sizes=(200, 60, 40, 30))
    cfg = arms.ArmConfig(
        rounds=12, batch_size=32, lr=0.3, seed=0, use_secagg=False,
        fl_local_steps=4,
        dp=DPConfig(clip_norm=1.0, noise_multiplier=0.7, microbatch_size=8),
    )
    from repro.models.tabular import pooled_accuracy

    fedavg = arms.run("fl", model, silos, cfg)
    scaffold = arms.run("scaffold", model, silos, cfg)
    acc_fedavg = pooled_accuracy(model, fedavg.params, silos)
    acc_scaffold = pooled_accuracy(model, scaffold.params, silos)
    assert acc_scaffold >= acc_fedavg - 0.05
