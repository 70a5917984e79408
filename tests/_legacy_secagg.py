"""Frozen snapshot of the pre-vectorization SecAgg mask loops.

This is the per-leaf, per-pair Python-loop implementation that
``repro.core.secagg`` shipped before the fused/vectorized hot path
(each unordered pair's pad generated twice — once with ``+`` by the lower
index, once with ``-`` by the higher — as O(H^2 * leaves) individual PRG
calls).  Kept verbatim as the reference the vectorized path is tested
against: mask cancellation is exact in the field, so the *aggregates* must
be bit-identical even though the pad values themselves differ.

Only the session internals are vendored, with the float64 NumPy field
encoding they used (``_encode``/``_decode``): the live module encodes on
the device in float32, and these stay the reference it is tested against
bit for bit.  The Shamir algebra is unchanged in the live module and
imported from it.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.secagg import (
    SecAggConfig,
    _FIELD_BITS,
    _FIELD_DTYPE,
    _SHAMIR_PRIME,
    _DH_GENERATOR,
    shamir_share,
    shamir_reconstruct,
)

PyTree = Any


def _encode(x, cfg: SecAggConfig) -> np.ndarray:
    """float -> field element (two's-complement embedding into uint32)."""
    q = np.round(np.asarray(x, np.float64) * cfg.scale).astype(np.int64)
    return (q % (1 << _FIELD_BITS)).astype(_FIELD_DTYPE)


def _decode(v: np.ndarray, cfg: SecAggConfig) -> np.ndarray:
    """field element -> float (centered: values >= 2^31 are negative)."""
    v = v.astype(np.int64)
    v = np.where(v >= (1 << (_FIELD_BITS - 1)), v - (1 << _FIELD_BITS), v)
    return (v.astype(np.float64) / cfg.scale).astype(np.float32)


def _pair_key(base: jax.Array, i: int, j: int) -> jax.Array:
    lo, hi = (i, j) if i < j else (j, i)
    return jax.random.fold_in(jax.random.fold_in(base, lo), hi)


def _prg_mask(key: jax.Array, shape: tuple[int, ...]) -> np.ndarray:
    return np.asarray(jax.random.bits(key, shape, dtype=jnp.uint32))


class LegacySecAggSession:
    """The historical honest-but-curious session, per-leaf loops."""

    def __init__(self, cfg: SecAggConfig, template: PyTree):
        self.cfg = cfg
        self._leaves, self._treedef = jax.tree_util.tree_flatten(template)
        self._base_key = jax.random.key(cfg.seed)

    def mask_for(self, i: int) -> list[np.ndarray]:
        masks = []
        for li, leaf in enumerate(self._leaves):
            key_leaf = jax.random.fold_in(self._base_key, 1000 + li)
            shape = tuple(np.shape(leaf))
            m = np.zeros(shape, _FIELD_DTYPE)
            with np.errstate(over="ignore"):
                for j in range(self.cfg.n_participants):
                    if j == i:
                        continue
                    pad = _prg_mask(_pair_key(key_leaf, i, j), shape)
                    m = (m + pad) if i < j else (m - pad)
            masks.append(m)
        return masks

    def upload(self, i: int, values: PyTree) -> list[np.ndarray]:
        leaves = jax.tree_util.tree_leaves(values)
        masks = self.mask_for(i)
        with np.errstate(over="ignore"):
            return [_encode(x, self.cfg) + m for x, m in zip(leaves, masks)]

    def aggregate(self, uploads: Sequence[list[np.ndarray]]) -> PyTree:
        total = [np.zeros(np.shape(x), _FIELD_DTYPE) for x in self._leaves]
        with np.errstate(over="ignore"):
            for up in uploads:
                total = [t + u for t, u in zip(total, up)]
        decoded = [jnp.asarray(_decode(t, self.cfg)) for t in total]
        return jax.tree_util.tree_unflatten(self._treedef, decoded)


def legacy_secure_sum(values: Sequence[PyTree], cfg: SecAggConfig) -> PyTree:
    session = LegacySecAggSession(cfg, values[0])
    uploads = [session.upload(i, v) for i, v in enumerate(values)]
    return session.aggregate(uploads)


class LegacyDropoutRobustSession:
    """The historical dropout-robust session, per-leaf recovery loops."""

    def __init__(self, cfg: SecAggConfig, template: PyTree, *,
                 threshold: int | None = None):
        n = cfg.n_participants
        self.cfg = cfg
        self.threshold = threshold if threshold is not None else n // 2 + 1
        self._leaves, self._treedef = jax.tree_util.tree_flatten(template)
        rng = np.random.default_rng(np.uint64(cfg.seed) ^ np.uint64(0x5ECA66))
        self._secret_keys = [
            int(rng.integers(2, _SHAMIR_PRIME - 1)) for _ in range(n)
        ]
        self.public_keys = [
            pow(_DH_GENERATOR, u, _SHAMIR_PRIME) for u in self._secret_keys
        ]
        self._shares = [
            shamir_share(u, n, self.threshold, rng) for u in self._secret_keys
        ]

    def _pair_seed(self, holder: int, other: int) -> int:
        return pow(
            self.public_keys[other], self._secret_keys[holder], _SHAMIR_PRIME
        )

    @staticmethod
    def _pad_from_seed(seed: int, leaf_index: int,
                       shape: tuple[int, ...]) -> np.ndarray:
        key = jax.random.fold_in(
            jax.random.key(seed % ((1 << 63) - 1)), leaf_index
        )
        return _prg_mask(key, shape)

    def upload(self, i: int, values: PyTree) -> list[np.ndarray]:
        leaves = jax.tree_util.tree_leaves(values)
        out = []
        with np.errstate(over="ignore"):
            for li, leaf in enumerate(leaves):
                shape = tuple(np.shape(self._leaves[li]))
                v = _encode(leaf, self.cfg)
                for j in range(self.cfg.n_participants):
                    if j == i:
                        continue
                    pad = self._pad_from_seed(self._pair_seed(i, j), li, shape)
                    v = (v + pad) if i < j else (v - pad)
                out.append(v)
        return out

    def aggregate(self, uploads: dict[int, list[np.ndarray]]) -> PyTree:
        n = self.cfg.n_participants
        survivors = sorted(uploads)
        dropped = [d for d in range(n) if d not in uploads]
        total = [np.zeros(np.shape(x), _FIELD_DTYPE) for x in self._leaves]
        with np.errstate(over="ignore"):
            for s in survivors:
                total = [t + u for t, u in zip(total, uploads[s])]
            for d in dropped:
                shares = [self._shares[d][j]
                          for j in survivors[: self.threshold]]
                u_d = shamir_reconstruct(shares)
                for j in survivors:
                    seed = pow(self.public_keys[j], u_d, _SHAMIR_PRIME)
                    for li in range(len(total)):
                        pad = self._pad_from_seed(
                            seed, li, tuple(np.shape(self._leaves[li]))
                        )
                        total[li] = (
                            total[li] - pad if j < d else total[li] + pad
                        )
        decoded = [jnp.asarray(_decode(t, self.cfg)) for t in total]
        return jax.tree_util.tree_unflatten(self._treedef, decoded)


def legacy_secure_sum_with_dropouts(
    values: Sequence[PyTree | None],
    cfg: SecAggConfig,
    *,
    threshold: int | None = None,
) -> PyTree:
    template = next(v for v in values if v is not None)
    session = LegacyDropoutRobustSession(cfg, template, threshold=threshold)
    uploads = {
        i: session.upload(i, v) for i, v in enumerate(values) if v is not None
    }
    return session.aggregate(uploads)
