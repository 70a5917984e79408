"""Room on the device for a model that fills a chip.

The reference keeps at most five model-sized trees on the device outside
its chunk program (``params``, the cohort's ``total``, the hospital's
``g_sum``, one chunk output or noise share, and the result being formed),
and the window keeps the parameters it follows on the host.

The stub kind here has leaves of shapes that no other array has, so that
``jax.live_arrays()`` counts its trees.  Run as a script on one chip, the
same stub at the leaf shapes of the Moonlight-16B-A3B cut (1 dense and 4
MoE layers, 8 of 64 experts, 20,480 vocabulary rows: 568,484,608 float32
parameters, 2.274 GB a tree) follows three rounds and prints the chip's
peak::

    PYTHONPATH=.:src python bench/tests/test_bench_room.py --secagg 1
"""

import argparse
import json
import math
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cohort, harness, reference

SEED = 2**31 + 11
# leaf shapes of the CPU tests: no other array of a run has one of these
SHAPES = {"emb": (37, 23), "head": (23, 11), "experts": (3, 7, 5)}


def moonlight_shapes() -> dict:
    """Leaf shapes of the Moonlight-16B-A3B cut: hidden 2048, MLA without a
    query LoRA (16 heads, qk 128+64, v 128, kv rank 512), a dense layer of
    width 11264, four MoE layers of 8 held experts of width 1408 with 2
    shared and a router over 64, 20,480 vocabulary rows."""
    d, heads, rank, rope = 2048, 16, 512, 64
    shapes = {"emb": (20480, d), "head": (d, 20480), "final_norm": (d,)}
    for layer in range(5):
        p = f"layer{layer}."
        shapes.update({
            p + "attn_norm": (d,), p + "w_q": (d, heads * (128 + rope)),
            p + "w_dkv": (d, rank + rope), p + "kv_norm": (rank,),
            p + "w_ukv": (rank, heads * (128 + 128)),
            p + "w_o": (heads * 128, d), p + "mlp_norm": (d,)})
        if layer == 0:
            shapes.update({p + "w_gate": (d, 11264), p + "w_up": (d, 11264),
                           p + "w_down": (11264, d)})
        else:
            shapes.update({
                p + "router": (d, 64), p + "router_bias": (64,),
                p + "experts_gate": (8, d, 1408),
                p + "experts_up": (8, d, 1408),
                p + "experts_down": (8, 1408, d),
                p + "shared_gate": (d, 2 * 1408),
                p + "shared_up": (d, 2 * 1408),
                p + "shared_down": (2 * 1408, d)})
    return shapes


def stub_model(shapes: dict, chunk: int):
    """A kind's ``reference`` over leaves of ``shapes``: an embedding bag
    through ``emb`` and ``head``, and every other leaf read at the row of
    the sequence's first token, so that each leaf has a gradient."""

    def init(seed):
        keys = jax.random.split(jax.random.key(seed), len(shapes))
        return {name: jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(shape[-1])
                for k, (name, shape) in zip(keys, sorted(shapes.items()))}

    def example_loss(params, x, y):
        h = jnp.mean(params["emb"][x], axis=0)
        loss = -jax.nn.log_softmax((h @ params["head"]).astype(
            jnp.float32))[y]
        for name, w in params.items():
            if name not in ("emb", "head"):
                row = w[x[0] % w.shape[0]]
                loss = loss + 1e-3 * jnp.mean(jnp.tanh(row)).astype(
                    jnp.float32)
        return loss

    return types.SimpleNamespace(init=init, rows=lambda raw: raw,
                                 example_loss=example_loss, chunk=chunk)


def stub_cohort(shapes: dict, sizes: list[int], seq_len: int, seed: int):
    """Token rows per hospital: ids below the vocabulary, labels below the
    head's width."""
    rng = np.random.default_rng(seed)
    vocab, classes = shapes["emb"][0], shapes["head"][1]
    raw = []
    for n in sizes:
        x = rng.integers(0, vocab, (n, seq_len), dtype=np.int32)
        raw.append((x, rng.integers(0, classes, n, dtype=np.int32)))
    return raw


def stub_config() -> dict:
    base = harness.load_cell("pancreas-secagg").config
    return {key: base[key]
            for key in ("lr", "dp", "secagg_frac_bits")}


def _live_per_leaf(shapes) -> int:
    """The most device buffers live in any one leaf's shape.  Buffers, not
    arrays: a host copy in flight holds a second array over its buffer."""
    live = {(a.shape, a.unsafe_buffer_pointer()) for a in jax.live_arrays()}
    return max(sum(shape == tuple(s) for shape, _ in live)
               for s in shapes.values())


@pytest.mark.parametrize("secagg", [True, False], ids=["secagg", "nosecagg"])
@pytest.mark.parametrize("chunk", [1, 2])
def test_reference_keeps_at_most_five_trees(monkeypatch, chunk, secagg):
    """Before and after every ``_tree_add``, ``_noise``, ``_quantize`` and
    chunk call, at most 5 buffers of each leaf's shape are live on the
    device.  The loop before this bound reads 8 here in all four cases,
    from the third hospital on (``params``, ``total``, ``noise_total``, the
    previous hospital's ``payload`` and ``noise``, ``g_sum``, the chunk's
    ``g`` and the sum being formed): 7 during a chunk call, the previous
    chunk's ``g`` among them, and 9 with the program's per-example gradient
    and output."""
    counts = []

    def counting(fn):
        def wrapped(*args, **kwargs):
            counts.append(_live_per_leaf(SHAPES))
            out = fn(*args, **kwargs)
            counts.append(_live_per_leaf(SHAPES))
            return out
        return wrapped

    for name in ("_tree_add", "_noise", "_quantize"):
        monkeypatch.setattr(reference, name,
                            counting(getattr(reference, name)))
    program = reference._chunk_program
    monkeypatch.setattr(reference, "_chunk_program",
                        lambda *args: counting(program(*args)))
    assert _live_per_leaf(SHAPES) == 0
    raw = stub_cohort(SHAPES, [30, 25, 20], 6, SEED)
    traj = reference.run(stub_model(SHAPES, chunk), raw, stub_config(),
                         {"batch_size": 12, "secagg": secagg}, SEED, 0, 3)
    assert all(traj.batches) and len(traj.params) == 4
    assert len(counts) > 40
    assert max(counts) == 5, counts


def _recording_on_round(monkeypatch):
    """Wrap ``Window.on_round``: a ``device_get`` of the parameters of
    every round it is handed, taken before the window sees them."""
    seen = {}
    on_round = harness.Window.on_round

    def wrapped(self, t, params):
        seen[t] = reference.flat(jax.device_get(params))
        on_round(self, t, params)

    monkeypatch.setattr(harness.Window, "on_round", wrapped)
    return seen


def _is_host(tree: dict) -> bool:
    return all(type(leaf) is np.ndarray for leaf in tree.values())


def _equal(a: dict, b: dict) -> bool:
    return a.keys() == b.keys() and all(np.array_equal(a[k], b[k])
                                        for k in a)


def test_window_keeps_the_followed_parameters_on_the_host(monkeypatch):
    """After ``drive``, the parameters after rounds 1 and 3 are host numpy
    arrays, and ``program_outputs`` reads the values the program handed
    the window (the window closing at round 3 collects that copy)."""
    seen = _recording_on_round(monkeypatch)
    cell = harness.load_cell("pancreas-secagg")
    cell.config = harness.load_kind(cell.config).tiny(cell.config)
    raw = cohort.cohort(cell.config, cell.traffic, SEED)
    report, window = harness.drive(cell, raw, SEED, 0.0,
                                   harness.CompileClock())
    last = harness.FOLLOWED_ROUNDS - 1
    assert set(window.kept) == {0, last} and window.copying is None
    assert all(_is_host(tree) for tree in window.kept.values())
    prog = harness.program_outputs(report, window)
    assert _equal(prog["p1"], seen[0]) and _equal(prog["p3"], seen[last])


def test_window_collects_round_3_at_the_next_round(monkeypatch):
    """A window still open after round 3 holds that round's device tree
    only until the next round, and then its host copy."""
    seen = _recording_on_round(monkeypatch)
    window = harness.Window(types.SimpleNamespace(epsilon_budget=1.0),
                            1e9, harness.CompileClock(), False)
    last = harness.FOLLOWED_ROUNDS - 1
    for t in range(last + 2):
        window.on_round(t, {"w": jnp.full((4, 3), float(t))})
        if t == last:
            assert window.copying is not None and last not in window.kept
    assert window.copying is None and window.t1 is None
    assert all(_is_host(tree) for tree in window.kept.values())
    assert _equal(window.kept[0], seen[0])
    assert _equal(window.kept[last], seen[last])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Follow three rounds of the "
                                 "stub at the Moonlight-16B-A3B cut's leaf "
                                 "shapes on one chip and print its peak.")
    ap.add_argument("--secagg", type=int, choices=(0, 1), required=True)
    ap.add_argument("--seed", type=int, default=SEED)
    args = ap.parse_args(argv)
    shapes = moonlight_shapes()
    n = sum(math.prod(s) for s in shapes.values())
    tree = 4 * n
    raw = stub_cohort(shapes, [200] * 5, 1024, args.seed)
    traffic = {"batch_size": 64, "secagg": bool(args.secagg)}
    t0 = time.perf_counter()
    try:
        traj = reference.run(stub_model(shapes, 1), raw, stub_config(),
                             traffic, args.seed, 0, 3)
        outcome = f"completed, batches {traj.batches}"
    except Exception as e:  # the chip's answer is what is measured
        outcome = f"failed: {type(e).__name__}: {str(e).splitlines()[0]}"
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(json.dumps({
        "parameters": n, "tree_bytes": tree, "secagg": bool(args.secagg),
        "outcome": outcome, "seconds": time.perf_counter() - t0,
        "peak_bytes_in_use": peak, "bytes_limit": stats.get("bytes_limit"),
        "peak_trees": None if peak is None else peak / tree,
        "device": jax.devices()[0].device_kind}), flush=True)
    return 0 if outcome.startswith("completed") else 1


if __name__ == "__main__":
    sys.exit(main())
