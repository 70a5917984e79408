"""The tabular MLP kind: the paper's pancreas and GEMINI classifiers.

A configuration of this kind gives ``model.sizes`` (the widths, input
first), ``model.task`` (``binary`` or ``multiclass``), ``task`` (the
cohort generator of ``bench/cohort.py`` that draws its hospitals),
``examples`` and ``hospitals``.  The traffic's keys are not read here.

The reference's model: He-normal weights and zero biases from
``jax.random.key(seed)`` (one split per layer, the first half of each
layer's split for ``w``); ReLU between layers; binary cross-entropy on one
logit or softmax cross-entropy; every hospital's features standardised by
the pooled mean and standard deviation (computed in float64), as the
paper's preparation phase does.
"""

from __future__ import annotations

import copy
import math
import types

import jax
import jax.numpy as jnp
import numpy as np

from bench import cohort as generators

# the cut a CPU test holds: widths and cohort size, by the configuration's task
TINY = {"pancreas": ([64, 32, 16, 4], 600), "gemini": ([40, 16, 8, 1], 800)}


def cohort(config: dict, traffic: dict, seed: int):
    """The hospitals' raw ``(x, y)`` arrays at the published widths and
    cohort size."""
    sizes = config["model"]["sizes"]
    task = config["task"]
    if task == "gemini":
        return generators.gemini(seed, config["examples"],
                                 config["hospitals"], sizes[0])
    if task == "pancreas":
        return generators.pancreas(seed, config["examples"],
                                   config["hospitals"], sizes[0], sizes[-1])
    raise ValueError(f"no generator for task {task!r}")


def program(config: dict, traffic: dict, raw):
    """The program's MLP and its hospitals, prepared by its own
    ``normalize_participants``."""
    import repro.arms as arms
    from repro.models import tabular

    silos = arms.normalize_participants(
        [arms.Participant(x, y) for x, y in raw])
    model = tabular.make_mlp_classifier(config["model"]["sizes"],
                                        task=config["model"]["task"])
    return model, silos


def init_params(sizes, seed: int) -> dict:
    keys = jax.random.split(jax.random.key(seed), len(sizes) - 1)
    params = {}
    for i in range(len(sizes) - 1):
        k_w, _ = jax.random.split(keys[i])
        w = jax.random.normal(k_w, (sizes[i], sizes[i + 1]), jnp.float32)
        params[f"l{i}"] = {"w": w * math.sqrt(2.0 / sizes[i]),
                           "b": jnp.zeros((sizes[i + 1],), jnp.float32)}
    return params


def standardize(raw):
    """Pooled mean and std of the features (float64 sums)."""
    n = sum(len(x) for x, _ in raw)
    s = sum(np.sum(x, axis=0, dtype=np.float64) for x, _ in raw)
    sq = sum(np.sum(np.square(x, dtype=np.float64), axis=0) for x, _ in raw)
    mean = s / n
    return mean, np.sqrt(np.maximum(sq / n - mean * mean, 0.0)) + 1e-8


def _example_loss(params, x, y, task: str, n_layers: int):
    h = x
    for i in range(n_layers):
        h = h @ params[f"l{i}"]["w"] + params[f"l{i}"]["b"]
        if i < n_layers - 1:
            h = jnp.maximum(h, 0)
    if task == "binary":
        z, y = h[0].astype(jnp.float32), y.astype(jnp.float32)
        return jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
    return -jax.nn.log_softmax(h.astype(jnp.float32))[y]


class _Standardized:
    """One hospital's features, standardised only in the rows read."""

    def __init__(self, x: np.ndarray, mean: np.ndarray, std: np.ndarray):
        self.x, self.mean, self.std = x, mean, std
        self.shape, self.dtype = x.shape, np.dtype(np.float32)

    def __getitem__(self, rows) -> np.ndarray:
        return ((self.x[rows] - self.mean) / self.std).astype(np.float32)


def _standardized_rows(raw):
    mean, std = standardize(raw)
    return [(_Standardized(x, mean, std), y) for x, y in raw]


def reference(config: dict, traffic: dict):
    """The plain model: ``init(seed)``, ``rows(raw)`` (standardised float32
    features and the labels, per hospital), ``example_loss`` and ``chunk``,
    the rows of one compiled chunk of per-example gradients."""
    sizes, task = config["model"]["sizes"], config["model"]["task"]

    def example_loss(params, x, y):
        return _example_loss(params, x, y, task, len(sizes) - 1)

    return types.SimpleNamespace(
        init=lambda seed: init_params(sizes, seed),
        rows=_standardized_rows, example_loss=example_loss, chunk=16)


def train_flops_per_example(config: dict, traffic: dict) -> int:
    """Forward and backward FLOPs of one example through the MLP: 2 per
    multiply-add forward, 4 backward (input and weight gradients), over
    every ``fan_in x fan_out`` weight matrix; biases and activations are
    not counted."""
    sizes = config["model"]["sizes"]
    return 6 * sum(a * b for a, b in zip(sizes[:-1], sizes[1:]))


def tiny(config: dict) -> dict:
    """``config`` with its widths and cohort cut to a CPU test's size."""
    config = copy.deepcopy(config)
    sizes, config["examples"] = TINY[config["task"]]
    config["model"]["sizes"] = list(sizes)
    return config
