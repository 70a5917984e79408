"""The per-kind seam: every configuration's kind has its module, a new kind
is a file alone, and the MLP kind feeds the reference what it fed before
the seam (digests of its first three rounds)."""

import hashlib
import json
import textwrap
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import cohort, compare, harness, reference

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
EXPORTS = ("cohort", "program", "reference", "train_flops_per_example",
           "tiny")
SEED = 2**31 + 11


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_every_config_kind_has_a_module(c):
    conf = json.loads((ROOT / c["file"]).read_text())
    kind = harness.load_kind(conf)
    assert all(callable(getattr(kind, name, None)) for name in EXPORTS)


def test_a_kind_without_a_module_names_the_path(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "KINDS", tmp_path)
    with pytest.raises(FileNotFoundError, match=str(tmp_path / "absent.py")):
        harness.load_kind({"model": {"kind": "absent"}})


# A kind of token rows: an embedding bag and a softmax head, plain
# jax.numpy on both sides; ``seq_len`` is the traffic's.
STUB = '''
import types

import jax
import jax.numpy as jnp
import numpy as np


def cohort(config, traffic, seed):
    rng = np.random.default_rng(seed)
    m = config["model"]
    raw = []
    for n in config["hospitals"]:
        x = rng.integers(0, m["vocab"], (n, traffic["seq_len"]),
                         dtype=np.int32)
        raw.append((x, (x[:, 0] % m["classes"]).astype(np.int32)))
    return raw


def _init(config, key):
    m = config["model"]
    k_emb, k_head = jax.random.split(key)
    return {"emb": jax.random.normal(k_emb, (m["vocab"], m["dim"])),
            "head": jax.random.normal(k_head, (m["dim"], m["classes"]))
            / m["dim"] ** 0.5}


def _logits(params, x):
    return jnp.mean(params["emb"][x], axis=-2) @ params["head"]


def example_loss(params, x, y):
    return -jax.nn.log_softmax(_logits(params, x).astype(jnp.float32))[y]


def program(config, traffic, raw):
    import repro.arms as arms

    model = arms.Model(
        init_fn=lambda key: _init(config, key),
        loss_fn=lambda params, ex: example_loss(params, ex["x"], ex["y"]),
        predict_fn=lambda params, x: jax.nn.softmax(_logits(params, x)))
    return model, [arms.Participant(x, y) for x, y in raw]


def reference(config, traffic):
    return types.SimpleNamespace(
        init=lambda seed: _init(config, jax.random.key(seed)),
        rows=lambda raw: raw, example_loss=example_loss, chunk=4)


def train_flops_per_example(config, traffic):
    m = config["model"]
    return 6 * m["dim"] * (traffic["seq_len"] + m["classes"])


def tiny(config):
    return config
'''


@pytest.fixture
def stub_cell(tmp_path, monkeypatch):
    (tmp_path / "stub.py").write_text(textwrap.dedent(STUB))
    monkeypatch.setattr(harness, "KINDS", tmp_path)
    base = harness.load_cell("pancreas-secagg").config
    config = {"name": "stub-tokens",
              "model": {"kind": "stub", "vocab": 50, "dim": 16, "classes": 5},
              "hospitals": [120, 90, 60], "lr": base["lr"], "dp": base["dp"],
              "secagg_frac_bits": base["secagg_frac_bits"],
              "accountant_orders": base["accountant_orders"]}
    limits = {"loss": 0.015, "grad_norm": 1.8e-4, "update_norm": 3e-4,
              "signal": 0.5, "epsilon": 1e-10}
    return harness.Cell(
        name="stub-tokens", config=config,
        traffic={"secagg": True, "batch_size": 16, "seq_len": 12},
        chips=1, limits=limits, per_layer=[])


def test_a_stub_kind_runs_as_a_file_alone(stub_cell):
    """A kind of int32 token rows runs through the whole cell, from its
    file in the kinds directory, and comes out correct; its bfloat16
    control does not."""
    result = harness.run_cell(stub_cell, SEED, 0.0, False,
                              time.perf_counter())
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0

    raw = cohort.cohort(stub_cell.config, stub_cell.traffic, SEED)
    assert raw[0][0].dtype == np.int32 and raw[0][0].shape[1] == 12
    values = harness.check(stub_cell, raw, SEED,
                           harness.control_outputs(stub_cell, raw, SEED))
    assert not compare.judge(values, stub_cell.limits), values


def digest(traj) -> str:
    h = hashlib.sha256()
    for tree in [*traj.params, traj.noise0]:
        for name, leaf in tree.items():
            h.update(name.encode())
            h.update(np.ascontiguousarray(leaf).tobytes())
    h.update(repr((traj.losses, traj.batches, traj.rate)).encode())
    return h.hexdigest()


# reference.run at the MLP kind's tiny cut, SEED, stream seed 0, three
# rounds, recorded before the model moved behind the kind seam
DIGESTS = {
    ("pancreas-secagg", "float32"):
        "c504f1d10b54716f97b6f7ce51cc686c6b9f3961662bd9e51803d50b3d5005f2",
    ("pancreas-secagg", "bfloat16"):
        "7121bae914ddbf19a088bf5da26b0d34090a7342a5a1bb6ead65f4db0fae2644",
    ("gemini-secagg", "float32"):
        "d55441681233be1dfa327c1789dd1398d425652b3c0e24cdf8636dae81f9cf3f",
    ("gemini-secagg", "bfloat16"):
        "ba1551af452a775801591194c72fae2faeba77297563a8fb44e6092b5496a8cf",
    ("pancreas-nosecagg", "float32"):
        "99fbf40c4c1c26de99c597de72dfa0404d8aedcb9448ae5ac7864a6b7322f245",
    ("pancreas-nosecagg", "bfloat16"):
        "350e19e2ee57e126d33cefde244341d714b2a766af28b62d160588faf8340621",
}


@pytest.mark.parametrize("name,dtype", sorted(DIGESTS))
def test_mlp_reference_rounds_match_their_digests(name, dtype):
    cell = harness.load_cell(name)
    kind = harness.load_kind(cell.config)
    config = kind.tiny(cell.config)
    raw = cohort.cohort(config, cell.traffic, SEED)
    traj = reference.run(kind.reference(config, cell.traffic), raw, config,
                         cell.traffic, SEED, 0, 3, getattr(jnp, dtype))
    assert digest(traj) == DIGESTS[name, dtype]


def parent_run(model, raw, config, traffic, seed, stream_seed, rounds,
               dtype=jnp.float32):
    """``reference.run`` as it was before it dropped each tree once added
    in (8-9 model-sized trees on the device), kept as the yardstick of its
    arithmetic."""
    from functools import partial

    import jax

    dp = config["dp"]
    frac_bits = config["secagg_frac_bits"]
    h = len(raw)
    rate = traffic["batch_size"] / sum(len(x) for x, _ in raw)
    fed = model.rows(raw)
    noise_std = (dp["clip_norm"] * dp["noise_multiplier"]
                 / jnp.sqrt(float(h)))
    chunk = jax.jit(partial(reference._clipped_chunk, model.example_loss,
                            float(dp["clip_norm"])))
    precision = "highest" if dtype == jnp.float32 else "default"
    params = jax.tree_util.tree_map(lambda a: a.astype(dtype),
                                    model.init(seed))
    traj = reference.Trajectory([reference.flat(params)], [], [], {}, rate)
    for t, picks in enumerate(reference.draws([len(x) for x, _ in raw],
                                              rate, stream_seed, rounds)):
        total = noise_total = None
        losses = []
        for i, idx in enumerate(picks):
            xs, ys = fed[i]
            g_sum = jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, jnp.float32), params)
            loss_sum = jnp.zeros((), jnp.float32)
            for lo in range(0, len(idx), model.chunk):
                rows = idx[lo:lo + model.chunk]
                x = np.zeros((model.chunk,) + xs.shape[1:], xs.dtype)
                x[:len(rows)] = xs[rows]
                y = np.zeros((model.chunk,) + ys.shape[1:], ys.dtype)
                y[:len(rows)] = ys[rows]
                mask = np.zeros((model.chunk,), np.float32)
                mask[:len(rows)] = 1.0
                with jax.default_matmul_precision(precision):
                    g, l = chunk(params, reference._feed(x, dtype), y, mask)
                g_sum = reference._tree_add(g_sum, g)
                loss_sum = loss_sum + l
            losses.append(float(loss_sum) / max(len(idx), 1))
            noise = reference._noise(stream_seed, t, i, g_sum, noise_std)
            payload = reference._tree_add(g_sum, noise)
            if traffic["secagg"]:
                payload = reference._quantize(payload, frac_bits)
            total = reference._tree_add(total, payload)
            noise_total = reference._tree_add(noise_total, noise)
        if traffic["secagg"]:
            total = reference._dequantize(total, frac_bits)
        batch = sum(len(idx) for idx in picks)
        if batch:
            params = jax.tree_util.tree_map(
                lambda p, g: (p.astype(jnp.float32)
                              - config["lr"] * (g / batch)).astype(dtype),
                params, total)
        traj.params.append(reference.flat(params))
        traj.losses.append(float(np.mean(losses)))
        traj.batches.append(batch)
        if t == 0:
            traj.noise0 = reference.flat(noise_total)
    return traj


def assert_same_trajectory(a, b):
    assert len(a.params) == len(b.params) == 4
    for pa, pb in zip([*a.params, a.noise0], [*b.params, b.noise0]):
        assert pa.keys() == pb.keys()
        for k in pa:
            assert pa[k].dtype == pb[k].dtype and np.array_equal(pa[k],
                                                                 pb[k]), k
    assert (a.losses, a.batches, a.rate) == (b.losses, b.batches, b.rate)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_reference_matches_the_parent_loop(name, dtype):
    """Every cell at its kind's tiny cut: the same trajectory, bit for bit,
    as the loop that kept every tree."""
    cell = harness.load_cell(name)
    kind = harness.load_kind(cell.config)
    config = kind.tiny(cell.config)
    raw = cohort.cohort(config, cell.traffic, SEED)
    args = (kind.reference(config, cell.traffic), raw, config, cell.traffic,
            SEED, 0, 3, getattr(jnp, dtype))
    assert_same_trajectory(reference.run(*args), parent_run(*args))


@pytest.mark.parametrize("secagg", [True, False], ids=["secagg", "nosecagg"])
def test_reference_matches_the_parent_loop_on_the_token_stub(stub_cell,
                                                             secagg):
    stub_cell.traffic["secagg"] = secagg
    kind = harness.load_kind(stub_cell.config)
    raw = cohort.cohort(stub_cell.config, stub_cell.traffic, SEED)
    args = (kind.reference(stub_cell.config, stub_cell.traffic), raw,
            stub_cell.config, stub_cell.traffic, SEED, 0, 3)
    assert_same_trajectory(reference.run(*args), parent_run(*args))
