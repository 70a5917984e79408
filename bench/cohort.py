"""The benchmark's own cohort generators.

A frozen copy of the pancreas and GEMINI generators of
``repro.data.synthetic`` (same draws for the same seed), kept here so that a
later change to the program's generator cannot move the yardstick; the MLP
kind (``bench/kinds/mlp.py``) picks one by the configuration's ``task``.
The program receives only the ``(x, y)`` arrays of each hospital.

``cohort(config, traffic, seed)`` returns one ``(x, y)`` pair of numpy
arrays per hospital, from the configuration's kind.
"""

from __future__ import annotations

import numpy as np


def _silo_props(published: np.ndarray, n_silos: int) -> np.ndarray:
    if n_silos <= len(published):
        props = published[:n_silos]
    else:
        tail = published.min() * 0.8 ** np.arange(
            1, n_silos - len(published) + 1)
        props = np.concatenate([published, tail])
    return props / props.sum()


def _solve_rate_bias(rng, w, d_latent, rate, z_std=1.0, n_probe=20000):
    z = rng.normal(0, z_std, (n_probe, d_latent))
    logits = np.sort(z @ w)
    return -logits[int((1 - rate) * n_probe)]


def gemini(seed: int, n_total: int, n_silos: int, n_features: int,
           mortality_rate: float = 0.17) -> list[tuple[np.ndarray, np.ndarray]]:
    """Hospitals of an EHR-like binary mortality task with size skew and
    per-hospital covariate shift (float32 features, float32 0/1 labels)."""
    rng = np.random.default_rng(seed)
    props = _silo_props(
        np.array([0.22, 0.18, 0.15, 0.12, 0.10, 0.09, 0.08, 0.06]), n_silos)
    d_latent = 24
    shift_std = 0.8
    w = rng.normal(0, 1.2, d_latent)
    proj = rng.normal(0, 1.0 / np.sqrt(d_latent), (d_latent, n_features))
    bias = _solve_rate_bias(rng, w, d_latent, mortality_rate,
                            z_std=float(np.sqrt(1.0 + shift_std**2)))
    silos = []
    for i in range(n_silos):
        n = max(16, int(n_total * props[i]))
        shift = rng.normal(0, shift_std, d_latent)
        z = rng.normal(0, 1, (n, d_latent)) + shift
        logits = z @ w + bias
        y = (logits + rng.logistic(0, 1, n) > 0).astype(np.float32)
        x = z @ proj + rng.normal(0, 0.5, (n, n_features))
        n_cat = n_features // 2
        x[:, :n_cat] = (x[:, :n_cat] > 0.8).astype(np.float32)
        silos.append((x.astype(np.float32), y))
    return silos


def pancreas(seed: int, n_total: int, n_silos: int, n_genes: int,
             n_types: int) -> list[tuple[np.ndarray, np.ndarray]]:
    """Studies of a single-cell-like cell-type task: log10 gene counts with
    marker genes per type and a per-study batch effect (int32 labels)."""
    rng = np.random.default_rng(seed)
    props = _silo_props(np.array([0.55, 0.20, 0.13, 0.02, 0.10]), n_silos)
    n_marker = 120
    markers = rng.choice(n_genes, (n_types, n_marker), replace=True)
    class_probs = np.array([0.45, 0.35, 0.07, 0.13])[:n_types]
    class_probs = class_probs / class_probs.sum()
    silos = []
    for i in range(n_silos):
        n = max(24, int(n_total * props[i]))
        y = rng.choice(n_types, n, p=class_probs)
        base = rng.poisson(0.3, (n, n_genes)).astype(np.float32)
        batch_effect = rng.normal(0, 0.15, n_genes)
        for c in range(n_types):
            rows = y == c
            base[np.ix_(rows, markers[c])] += rng.poisson(
                6.0, (rows.sum(), n_marker))
        x = np.log10(base + 1.0) + batch_effect
        silos.append((x.astype(np.float32), y.astype(np.int32)))
    return silos


def cohort(config: dict, traffic: dict,
           seed: int) -> list[tuple[np.ndarray, np.ndarray]]:
    """The hospitals' raw data for ``config`` (a ``bench/configs`` file)
    under ``traffic``, drawn by its kind."""
    from bench import harness

    return harness.load_kind(config).cohort(config, traffic, seed)
