"""Plain reference of the first rounds of a DeCaPH run on the ideal backend.

Follows the protocol the configuration states, written from the paper's
Steps 1-7 and independent of the program under test.  The model is the
configuration's kind's (``bench/kinds/<kind>.py``, its ``reference``): its
weights from ``seed``, the rows it feeds (its preparation phase, such as
the MLP's pooled standardisation) and its per-example loss.  Here is the
DP round and nothing of the model:

* round ``t``: Poisson sampling at the shared rate ``B / N`` from one host
  ``numpy.random.default_rng(stream_seed)`` stream, hospitals in ascending
  order; per-example gradients clipped to ``C`` by their global L2 norm and
  summed; each hospital adds its noise share N(0, (C sigma)^2 / H) drawn from
  ``fold_in(fold_in(key(stream_seed), 17 + t), hospital)``, one split per leaf in
  pytree order; the shares are summed in ascending hospital order (with
  SecAgg: each fixed-point encoded with ``frac_bits`` fractional bits, the
  codes summed and decoded, since the pairwise masks cancel in the field
  sum); the sum is divided by the aggregate batch and applied by SGD.

``dtype`` float32 runs the model's arithmetic at ``highest`` matmul
precision; bfloat16 is the control, which has to fail the comparison
(floating rows are fed in ``dtype``, integer rows such as token ids as
they are).  Rows are processed in chunks of the kind's ``chunk`` rows, so
that a published-width model's per-example gradients fit.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

NOISE_SALT = 17


def names(tree) -> list[str]:
    """Leaf names (``"l0.w"``, ...) in pytree order."""
    return [".".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def flat(tree) -> dict[str, np.ndarray]:
    """``{"l0.w": array, ...}`` host copy of a parameter tree."""
    host = jax.device_get(tree)
    return dict(zip(names(host), map(np.asarray,
                                     jax.tree_util.tree_leaves(host))))


def _clipped_chunk(example_loss, clip, params, x, y, mask):
    """(sum of clipped per-example gradients, sum of losses) over a chunk."""

    def one(x_i, y_i):
        loss, g = jax.value_and_grad(example_loss)(params, x_i, y_i)
        leaves = jax.tree_util.tree_leaves(g)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(a.astype(jnp.float32)))
                            for a in leaves))
        factor = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
        return jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32) * factor, g), loss

    g, losses = jax.vmap(one)(x, y)
    g = jax.tree_util.tree_map(
        lambda a: jnp.tensordot(mask, a, axes=1,
                                precision=jax.lax.Precision.HIGHEST), g)
    return g, jnp.sum(losses * mask)


def _noise(seed: int, t: int, idx: int, template, std):
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(seed),
                                                NOISE_SALT + t), idx)
    leaves, treedef = jax.tree_util.tree_flatten(template)
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        jax.random.normal(k, a.shape, jnp.float32) * std
        for k, a in zip(keys, leaves)])


def _quantize(tree, frac_bits: int):
    """Fixed-point encode: ``round(x * 2^frac_bits)`` as int32.  Exact for
    these payloads (|x| < 2^15), so the int32 sum wraps as Z_2^32 does."""
    scale = float(1 << frac_bits)
    return jax.tree_util.tree_map(
        lambda a: jnp.round(a * scale).astype(jnp.int32), tree)


def _dequantize(tree, frac_bits: int):
    scale = float(1 << frac_bits)
    return jax.tree_util.tree_map(
        lambda a: (a.astype(jnp.float32) / scale), tree)


def _tree_add(a, b):
    return b if a is None else jax.tree_util.tree_map(jnp.add, a, b)


def _host_add(a: dict, b: dict) -> dict:
    """``a + b`` leaf by leaf on flat host dicts (``b`` where ``a`` is
    empty): float32 adds round as ``jnp.add`` does."""
    return {k: a[k] + b[k] for k in b} if a else b


@dataclasses.dataclass
class Trajectory:
    """What the reference (or the control) produced over its rounds.

    ``params[t]`` is the flat parameter dict after ``t`` rounds (0 = init),
    ``losses[t]`` the mean over hospitals of each one's mean loss on its
    draw, ``batches[t]`` the aggregate batch, ``noise0`` the sum of the
    hospitals' noise shares in the first round (flat dict).
    """

    params: list[dict]
    losses: list[float]
    batches: list[int]
    noise0: dict
    rate: float


def draws(sizes: list[int], rate: float, seed: int, rounds: int):
    """Per round, per hospital: the indices of its Poisson draw."""
    rng = np.random.default_rng(seed)
    return [[np.nonzero(rng.random(n) < rate)[0] for n in sizes]
            for _ in range(rounds)]


def _feed(x: np.ndarray, dtype):
    return (jnp.asarray(x, dtype) if np.issubdtype(x.dtype, np.floating)
            else jnp.asarray(x))


def _chunk_program(example_loss, clip: float):
    """The compiled chunk: ``(params, x, y, mask) -> (g, loss_sum)``."""
    return jax.jit(partial(_clipped_chunk, example_loss, clip))


def run(model, raw, config: dict, traffic: dict, seed: int,
        stream_seed: int, rounds: int, dtype=jnp.float32) -> Trajectory:
    """Follow ``rounds`` rounds of ``model`` (a kind's ``reference``) from
    the weights of ``seed`` and the draws and noise of ``stream_seed``; see
    the module docstring.  ``model.rows(raw)`` gives each hospital's
    ``(x, y)``: arrays, or objects with ``shape``, ``dtype`` and indexing
    by an array of row numbers.

    At most five model-sized trees are on the device at a time outside the
    chunk program: ``params``, the cohort's ``total``, the hospital's
    ``g_sum``, one chunk output or noise share, and the result being formed.
    Each tree is dropped as soon as it has been added in; the round-1 noise
    sum is kept on the host alone."""
    dp = config["dp"]
    frac_bits = config["secagg_frac_bits"]
    h = len(raw)
    rate = traffic["batch_size"] / sum(len(x) for x, _ in raw)
    fed = model.rows(raw)
    noise_std = (dp["clip_norm"] * dp["noise_multiplier"]
                 / jnp.sqrt(float(h)))
    chunk = _chunk_program(model.example_loss, float(dp["clip_norm"]))
    precision = "highest" if dtype == jnp.float32 else "default"
    params = jax.tree_util.tree_map(lambda a: a.astype(dtype),
                                    model.init(seed))
    traj = Trajectory([flat(params)], [], [], {}, rate)
    for t, picks in enumerate(draws([len(x) for x, _ in raw], rate,
                                    stream_seed, rounds)):
        total = None
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
                    g, l = chunk(params, _feed(x, dtype), y, mask)
                g_sum = _tree_add(g_sum, g)
                del g
                loss_sum = loss_sum + l
            losses.append(float(loss_sum) / max(len(idx), 1))
            noise = _noise(stream_seed, t, i, g_sum, noise_std)
            if t == 0:   # ascending hospital order, on the host
                traj.noise0 = _host_add(traj.noise0, flat(noise))
            payload = _tree_add(g_sum, noise)
            del g_sum, noise
            if traffic["secagg"]:
                payload = _quantize(payload, frac_bits)
            total = _tree_add(total, payload)   # ascending hospital order
            del payload
        if traffic["secagg"]:
            total = _dequantize(total, frac_bits)
        batch = sum(len(idx) for idx in picks)
        if batch:
            params = jax.tree_util.tree_map(
                lambda p, g: (p.astype(jnp.float32)
                              - config["lr"] * (g / batch)).astype(dtype),
                params, total)
        del total
        traj.params.append(flat(params))
        traj.losses.append(float(np.mean(losses)))
        traj.batches.append(batch)
    return traj
