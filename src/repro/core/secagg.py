"""Secure Aggregation (Bonawitz et al. 2017 style) in fixed-point arithmetic.

DeCaPH uses SecAgg in three places (paper Methods): (1) global feature
mean/variance at preparation, (2) aggregate mini-batch size per round,
(3) the gradient aggregation itself.  We implement the honest-but-curious
variant faithfully:

  * values are quantised to a finite field Z_{2^32} (fixed point, ``frac_bits``
    fractional bits),
  * every ordered pair (i < j) of participants derives a shared one-time pad
    from a pairwise PRG seed (``jax.random.fold_in`` stands in for the DH key
    agreement — both are PRF expansions of a shared secret),
  * participant i uploads  x_i + sum_{j>i} PRG(s_ij) - sum_{j<i} PRG(s_ji)
    (mod 2^32); masks cancel *exactly* in the field sum, so the aggregator
    only ever learns the total.

Two session flavours:

  * ``SecAggSession`` — the paper's honest-but-curious variant: hospitals
    follow the protocol and stay online, so every upload must arrive
    (``aggregate`` fails loudly otherwise — a missing upload would leave
    un-cancelled masks and a silently corrupt sum).
  * ``DropoutRobustSession`` — Bonawitz-style dropout recovery: every
    participant derives its pairwise pads from a real Diffie-Hellman
    exchange (toy 61-bit group standing in for X25519) and Shamir
    secret-shares its DH secret among the cohort at setup.  When a
    participant drops before uploading, any ``threshold`` survivors can
    reveal their shares, the facilitator reconstructs the dropped secret,
    regenerates the survivor-side pads involving the dropped party, and
    cancels them — the sum of the *surviving* uploads is recovered exactly.
    ``repro.sim`` injects dropouts against this path.

Exactness (mask cancellation) is property-tested in ``tests/test_secagg.py``;
dropout recovery in ``tests/test_secagg_dropout.py``.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import repro.obs as obs

PyTree = Any
Shapes = tuple[tuple[int, ...], ...]  # a template's leaf shapes

_FIELD_DTYPE = np.uint32
_FIELD_BITS = 32

# Field arithmetic runs on the device, in jitted programs over uint32 arrays
# (DESIGN.md §7): uint32 adds and sums wrap mod 2^32 exactly, and the
# fixed-point encode/decode below is exact in float32, so no value needs
# jax_enable_x64 and a payload the device computed never comes to the host.


# Pairs per chunk of the in-jit mask accumulation: bounds resident pad
# memory at ``pad_chunk_pairs * L * 4`` bytes instead of the full
# O(H^2 * L) pad matrix (~5 GB at H=50 on a 1M-param model).
_DEFAULT_PAD_CHUNK = 1024


@dataclasses.dataclass(frozen=True)
class SecAggConfig:
    n_participants: int
    frac_bits: int = 16  # fixed-point fractional bits
    seed: int = 0
    pad_chunk_pairs: int = _DEFAULT_PAD_CHUNK  # memory knob, never numerics

    @property
    def scale(self) -> float:
        return float(1 << self.frac_bits)


_TWO_31 = float(1 << (_FIELD_BITS - 1))
_TWO_32 = float(1 << _FIELD_BITS)
_MINUS_ONE = (1 << _FIELD_BITS) - 1  # -1 in the field


def _field_encode(x: jax.Array, frac_bits: int) -> jax.Array:
    """float -> field element (two's-complement embedding into uint32),
    traced.  ``r = round(x * 2^frac_bits)`` is exact in float32 (the scale
    is a power of two, ``jnp.round`` ties to even like ``np.round``); r
    below 2^31 in magnitude is its own int32, and a larger r is a multiple
    of ulp(r) >= 2^8, so ``r mod 2^32``, centred into int32's range, is
    exact in float32 too.  Bit-identical to the float64 NumPy encode for
    every finite float32 x whose r fits int64 (``tests/_legacy_secagg.py``
    keeps that reference)."""
    r = jnp.round(x.astype(jnp.float32) * float(1 << frac_bits))
    wrapped = r - _TWO_32 * jnp.floor(r * (1.0 / _TWO_32))  # [0, 2^32)
    wrapped = jnp.where(wrapped >= _TWO_31, wrapped - _TWO_32, wrapped)
    centred = jnp.where(jnp.abs(r) < _TWO_31, r, wrapped)
    return jax.lax.bitcast_convert_type(centred.astype(jnp.int32), jnp.uint32)


def _field_decode(v: jax.Array, frac_bits: int) -> jax.Array:
    """field element -> float32 (centred: values >= 2^31 are negative),
    traced.  One rounding of v / 2^frac_bits, as the float64 NumPy decode:
    the power-of-two scale is exact."""
    signed = jax.lax.bitcast_convert_type(v, jnp.int32)
    return signed.astype(jnp.float32) * (1.0 / float(1 << frac_bits))


# -- vectorized, chunked pair-pad machinery (DESIGN.md §7) -------------------
#
# Mask generation is the round's O(H^2 * leaves) hot spot when done naively:
# every (participant, peer, leaf) triple used to be its own fold_in + PRG
# dispatch, and each unordered pair's pad was generated twice (once with
# ``+`` by the lower index, once with ``-`` by the higher).  The vectorized
# path generates the pad of every unordered pair {lo, hi} exactly ONCE per
# round, and — rather than materialising the O(H^2 * L) pad matrix (~5 GB at
# H=50 on a 1M-param model) — accumulates the signed net-mask rows in-jit
# over chunks of ``pad_chunk_pairs`` pairs: each chunk's pads are generated
# by one batched PRG call, scatter-added with the sign convention (lo adds,
# hi subtracts — every pad appears exactly once with each sign and cancels
# in the field sum), and freed before the next chunk.  Field addition in
# Z_2^32 is exactly associative/commutative, so chunking changes no bit;
# the legacy per-leaf loop survives as a reference implementation in
# ``tests/_legacy_secagg.py`` and aggregates stay bit-identical to it.
#
# A pad, a net mask and a ciphertext are lists of per-leaf arrays in the
# template's leaf shapes (a leading participant or pair axis where there
# is one), never one flat length-L vector: the TPU tiles an array's last
# two dimensions, so a flat vector cut into 2-D leaves is a relayout that
# its compiler unrolls row by row — seconds to minutes of compile and tens
# to hundreds of MB of code for a 15558 x 1000 leaf.


def _pairs(n: int) -> tuple[np.ndarray, np.ndarray]:
    """Index arrays (los, his) over the n*(n-1)/2 unordered pairs, lo < hi."""
    lo, hi = np.triu_indices(n, k=1)
    return lo.astype(np.uint32), hi.astype(np.uint32)


def _pad_leaves(key: jax.Array, shapes: Shapes) -> list[jax.Array]:
    """One pad: a uint32 array per leaf shape, leaf li keyed by
    ``fold_in(key, li)``."""
    return [
        jax.random.bits(jax.random.fold_in(key, li), shape, dtype=jnp.uint32)
        for li, shape in enumerate(shapes)
    ]


def _leading(v: jax.Array, ndim: int) -> jax.Array:
    """A per-row vector shaped to broadcast over rows of rank ``ndim``."""
    return v.reshape((-1,) + (1,) * (ndim - 1))


def _scatter_signed(masks, pads, lo_c, hi_c, v_c):
    """Add each pad row to its lo participant's net mask and subtract it
    from its hi participant's (uint32: exact two's complement); padding
    rows (``v_c`` 0) add nothing."""
    out = []
    for m, p in zip(masks, pads):
        p = p * _leading(v_c, p.ndim)
        out.append(m.at[lo_c].add(p).at[hi_c].add(-p))
    return out


@partial(jax.jit, static_argnums=(4, 5))
def _pair_mask_scan(
    base_key: jax.Array, los: jax.Array, his: jax.Array, valid: jax.Array,
    n: int, shapes: Shapes,
) -> list[jax.Array]:
    """Per-leaf (n, ...) signed net masks from (n_chunks, C) pair-index
    chunks."""

    def body(masks, inp):
        lo_c, hi_c, v_c = inp

        def one(lo, hi):
            k = jax.random.fold_in(jax.random.fold_in(base_key, lo), hi)
            return _pad_leaves(k, shapes)

        pads = jax.vmap(one)(lo_c, hi_c)
        return _scatter_signed(masks, pads, lo_c, hi_c, v_c), None

    masks0 = [jnp.zeros((n,) + shape, jnp.uint32) for shape in shapes]
    masks, _ = jax.lax.scan(body, masks0, (los, his, valid))
    return masks


_SEED_PAD_KEY = jax.random.key(0x5ECA66)


def _seed_pads(hi, lo, shapes: Shapes) -> list[jax.Array]:
    """One pad from a DH agreement split into 32-bit words.

    This is THE derivation: the masking scan and the dropout-recovery path
    must produce bit-identical pads from the same seed words, or survivors'
    regenerated pads no longer cancel a dropped party's masks — so both go
    through this one function.
    """
    k = jax.random.fold_in(jax.random.fold_in(_SEED_PAD_KEY, hi), lo)
    return _pad_leaves(k, shapes)


@partial(jax.jit, static_argnums=(5, 6))
def _seed_mask_scan(
    hi_words: jax.Array, lo_words: jax.Array,
    los: jax.Array, his: jax.Array, valid: jax.Array,
    n: int, shapes: Shapes,
) -> list[jax.Array]:
    """Per-leaf (n, ...) signed net masks from chunked DH-seed words (the
    seed, not the pair indices, keys the PRG — so a pad can be regenerated
    from a Shamir-reconstructed secret during recovery)."""

    def body(masks, inp):
        hw_c, lw_c, lo_c, hi_c, v_c = inp
        pads = jax.vmap(lambda hi, lo: _seed_pads(hi, lo, shapes))(hw_c, lw_c)
        return _scatter_signed(masks, pads, lo_c, hi_c, v_c), None

    masks0 = [jnp.zeros((n,) + shape, jnp.uint32) for shape in shapes]
    masks, _ = jax.lax.scan(
        body, masks0, (hi_words, lo_words, los, his, valid)
    )
    return masks


def _chunked(arrs: Sequence[np.ndarray], chunk: int) -> list[np.ndarray]:
    """Zero-pad each 1-D array to a chunk multiple and reshape to chunks,
    plus a trailing validity row-mask for the padding."""
    n_items = len(arrs[0])
    c = max(1, min(int(chunk), n_items))
    n_chunks = -(-n_items // c)
    pad = n_chunks * c - n_items

    def shape(a):
        return np.concatenate(
            [a, np.zeros((pad,), a.dtype)]
        ).reshape(n_chunks, c)

    valid = shape(np.ones((n_items,), np.uint32))
    return [shape(a) for a in arrs] + [valid]


def _signed_masks(
    n: int,
    shapes: Shapes,
    los: np.ndarray,
    his: np.ndarray,
    *,
    chunk: int = _DEFAULT_PAD_CHUNK,
    base_key: jax.Array | None = None,
    seeds: Sequence[int] | None = None,
) -> list[jax.Array]:
    """Per-leaf (n, ...) net masks on the device: row i = sum_{i=lo} pad -
    sum_{i=hi} pad (mod 2^32), accumulated in-jit over ``chunk``-pair
    slices; exactly one of ``base_key`` (pair-index keyed pads) / ``seeds``
    (DH-agreement keyed pads) selects the PRG family."""
    if len(los) == 0:
        return [jnp.zeros((n,) + shape, jnp.uint32) for shape in shapes]
    if seeds is not None:
        hi_w, lo_w = _seed_words(seeds)
        hw, lw, lo_c, hi_c, valid = _chunked([hi_w, lo_w, los, his], chunk)
        return _seed_mask_scan(hw, lw, lo_c, hi_c, valid, n, shapes)
    lo_c, hi_c, valid = _chunked([los, his], chunk)
    return _pair_mask_scan(base_key, lo_c, hi_c, valid, n, shapes)


def _seed_words(seeds: Sequence[int]) -> tuple[np.ndarray, np.ndarray]:
    arr = [int(s) for s in seeds]
    hi = np.asarray([s >> 32 for s in arr], np.uint32)
    lo = np.asarray([s & 0xFFFFFFFF for s in arr], np.uint32)
    return hi, lo


@partial(jax.jit, static_argnums=(2,))
def _batched_seed_pads(
    hi_words: jax.Array, lo_words: jax.Array, shapes: Shapes
) -> list[jax.Array]:
    """Per-leaf (n_seeds, ...) pads from DH-seed words — the *recovery*
    path, where the handful of survivor-side pads of a dropped party really
    is needed (to cancel them from the ciphertext sum).  Same
    ``_seed_pads`` derivation as the masking scan, by construction."""
    return jax.vmap(
        lambda hi, lo: _seed_pads(hi, lo, shapes)
    )(hi_words, lo_words)


# -- the field programs ------------------------------------------------------
#
# A cohort's uploads are per-leaf (n, ...) uint32 ciphertext stacks: row s
# of every leaf is the s-th uploading participant's payload leaf encoded
# into the field plus that participant's net mask.  The aggregator sums each leaf
# over the participant axis (the masks cancel), adds back any recovered
# pads and decodes.  Two entry points each: the payload stack as the fused
# cohort program leaves it (``secure_sum_stack``), and per-participant
# trees and ciphertext lists, stacked and split inside the same program
# (``upload``/``upload_all``/``aggregate``, the form the sim transport
# ships).  Every one is plain ``jax.jit``: the fused round's O(1)-dispatch
# count (``repro.instrument``) covers the cohort program only.


@partial(jax.jit, static_argnums=(2,))
def _cohort_ciphertexts(
    stacks: Sequence[jax.Array], masks: Sequence[jax.Array], frac_bits: int,
) -> list[jax.Array]:
    """Per-leaf (n, ...) ciphertext stacks from (n, ...) payload stacks and
    the same rows' per-leaf net masks."""
    return [_field_encode(x, frac_bits) + m  # uint32: wraps mod 2^32
            for x, m in zip(stacks, masks)]


@partial(jax.jit, static_argnums=(2,))
def _participant_ciphertexts(
    cohort: Sequence[Sequence[jax.Array]], masks: Sequence[jax.Array],
    frac_bits: int,
) -> list[list[jax.Array]]:
    """Per-participant ciphertexts (uint32 leaf lists) from per-participant
    payload leaves: ``_cohort_ciphertexts`` between a stack and a split."""
    stacks = [jnp.stack(col) for col in zip(*cohort)]
    cts = _cohort_ciphertexts(stacks, masks, frac_bits)
    return [[c[s] for c in cts] for s in range(len(cohort))]


def _sum_decode(ciphertexts, recovered, frac_bits: int) -> list[jax.Array]:
    """Field sum of each leaf's (m, ...) ciphertext rows, plus the signed
    recovered pads ``(pads, signs)`` if any, decoded to float32."""
    totals = [jnp.sum(c, axis=0, dtype=jnp.uint32) for c in ciphertexts]
    if recovered is not None:
        pads, signs = recovered  # signs: 1 or 2^32 - 1 (= -1 in the field)
        totals = [t + jnp.sum(p * _leading(signs, p.ndim), axis=0,
                              dtype=jnp.uint32)
                  for t, p in zip(totals, pads)]
    return [_field_decode(t, frac_bits) for t in totals]


_field_sum = jax.jit(_sum_decode, static_argnums=(2,))


@partial(jax.jit, static_argnums=(2,))
def _participant_field_sum(uploads, recovered, frac_bits: int):
    """``_field_sum`` over per-participant ciphertext lists, stacked into
    per-leaf rows inside the program."""
    return _sum_decode([jnp.stack(col) for col in zip(*uploads)],
                       recovered, frac_bits)


def _masked_uploads(session, program, payloads, rows: Sequence[int]):
    """Run a ciphertext ``program`` over ``payloads`` for the ascending
    participants ``rows``, under the ``secagg.upload`` span (the mask
    program's and the encode program's dispatches)."""
    with obs.span("secagg.upload", cat="secagg", participants=len(rows),
                  length=session._length):
        masks = session._net_masks()
        if len(rows) != session.cfg.n_participants:  # a subset's rows
            masks = [m[np.asarray(rows)] for m in masks]
        with obs.span("secagg.encode", cat="secagg"):
            return program(payloads, masks, session.cfg.frac_bits)


def _masked_cohort_uploads(
    session, values: Mapping[int, PyTree]
) -> dict[int, list[jax.Array]]:
    """Shared ``upload``/``upload_all`` body: every participant's
    ciphertext from one encode-and-mask program on the device."""
    if not values:
        return {}
    order = sorted(values)
    cohort = [jax.tree_util.tree_leaves(values[i]) for i in order]
    _check_leaves(cohort, session._shapes, "payload")
    uploads = _masked_uploads(session, _participant_ciphertexts, cohort,
                              order)
    return dict(zip(order, uploads))


def _session_masks(session, **prg) -> list[jax.Array]:
    """The session's per-leaf (n, ...) net masks under a ``secagg.masks``
    span: the pad program's dispatch; the masks stay on the device.
    ``prg`` is ``_signed_masks``'s ``base_key`` or ``seeds``."""
    t0 = obs.now()
    masks = _signed_masks(
        session.cfg.n_participants, session._shapes,
        session._los, session._his,
        chunk=session.cfg.pad_chunk_pairs, **prg,
    )
    if t0 is not None:
        obs.complete("secagg.masks", t0, cat="secagg",
                     pairs=len(session._los), bytes=0)
    return masks


def _decoded_tree(session, program, ciphertexts, recovered,
                  t0: float | None) -> PyTree:
    """Run a field-sum ``program`` (sum, recovered pads, decode) and close
    the ``secagg.sum`` span opened at ``t0``; the decoded leaves stay on
    the device, in the template's structure."""
    leaves = program(ciphertexts, recovered, session.cfg.frac_bits)
    if t0 is not None:
        obs.complete("secagg.sum", t0, cat="secagg", bytes=0)
    return jax.tree_util.tree_unflatten(session._treedef, leaves)


def _template_layout(session, template: PyTree) -> None:
    """The template's structure, leaf shapes and field length."""
    leaves, session._treedef = jax.tree_util.tree_flatten(template)
    session._shapes = tuple(tuple(np.shape(x)) for x in leaves)
    session._length = sum(math.prod(s) for s in session._shapes)


class SecAggSession:
    """One aggregation round over a fixed pytree template."""

    def __init__(self, cfg: SecAggConfig, template: PyTree):
        self.cfg = cfg
        self.template = template
        _template_layout(self, template)
        self._base_key = jax.random.key(cfg.seed)
        self._los, self._his = _pairs(cfg.n_participants)
        self._masks: list[jax.Array] | None = None  # built lazily

    def _net_masks(self) -> list[jax.Array]:
        """Every participant's net mask, accumulated over pair chunks."""
        if self._masks is None:
            self._masks = _session_masks(self, base_key=self._base_key)
        return self._masks

    def mask_for(self, i: int) -> list[np.ndarray]:
        """Net mask participant i applies (sums to zero over participants)."""
        return [np.asarray(m)[i] for m in self._net_masks()]

    def upload(self, i: int, values: PyTree) -> list[jax.Array]:
        """Masked ciphertext participant i sends to the leader."""
        return _masked_cohort_uploads(self, {i: values})[i]

    def upload_all(
        self, values: Mapping[int, PyTree]
    ) -> dict[int, list[jax.Array]]:
        """Ciphertexts for a whole cohort: one masking program on the
        device (participant index -> masked ciphertext)."""
        return _masked_cohort_uploads(self, values)

    def aggregate(self, uploads: Sequence[list[jax.Array]]) -> PyTree:
        """Leader-side sum of ciphertexts; masks cancel exactly in Z_2^32."""
        if len(uploads) != self.cfg.n_participants:
            raise ValueError(
                "honest-but-curious SecAgg requires all participants "
                f"({len(uploads)} of {self.cfg.n_participants} uploads); a "
                "missing upload leaves un-cancelled masks in the sum — use "
                "DropoutRobustSession if participants may drop out"
            )
        t0 = obs.now()
        _check_leaves(uploads, self._shapes, "upload")
        return _decoded_tree(self, _participant_field_sum, list(uploads),
                             None, t0)


def _check_leaves(
    rows: Sequence[Sequence[Any]], shapes: Shapes, what: str
) -> None:
    """Fail loudly on short/misshapen payloads or ciphertexts (the
    silent-garbage guard): each row's leaves must be the template's."""
    for k, row in enumerate(rows):
        if len(row) != len(shapes):
            raise ValueError(
                f"{what} {k} has {len(row)} leaves, template has "
                f"{len(shapes)} — truncated or mis-structured {what}"
            )
        for li, (u, shape) in enumerate(zip(row, shapes)):
            if tuple(np.shape(u)) != shape:
                raise ValueError(
                    f"{what} {k} leaf {li} shape {np.shape(u)} != template "
                    f"shape {shape}"
                )


def secure_sum(values: Sequence[PyTree], cfg: SecAggConfig) -> PyTree:
    """Convenience: full round (upload + aggregate) over a list of pytrees."""
    values = list(values)
    if not values:
        raise ValueError("secure_sum: empty value list")
    if len(values) != cfg.n_participants:
        raise ValueError(
            f"secure_sum: {len(values)} value trees for "
            f"{cfg.n_participants} participants — every participant must "
            "contribute (dropouts need DropoutRobustSession)"
        )
    session = SecAggSession(cfg, values[0])
    uploads = session.upload_all(dict(enumerate(values)))
    return session.aggregate([uploads[i] for i in range(len(values))])


def secure_sum_stack(stack: PyTree, cfg: SecAggConfig) -> PyTree:
    """``secure_sum`` over a cohort's payloads held as one (n, ...) stack
    per leaf, row s participant s's — the fused cohort program's output,
    masked, summed and decoded where it lies: the uploads are per-leaf
    (n, ...) ciphertext stacks on the device."""
    leaves, treedef = jax.tree_util.tree_flatten(stack)
    n = cfg.n_participants
    if not leaves or any(np.shape(x)[:1] != (n,) for x in leaves):
        raise ValueError(
            f"secure_sum_stack: every leaf needs a leading axis of the "
            f"{n} participants' rows (dropouts need DropoutRobustSession)"
        )
    session = SecAggSession(cfg, jax.tree_util.tree_unflatten(treedef, [
        jax.ShapeDtypeStruct(np.shape(x)[1:], x.dtype) for x in leaves
    ]))
    ciphertexts = _masked_uploads(session, _cohort_ciphertexts, leaves,
                                  range(n))
    return _decoded_tree(session, _field_sum, ciphertexts, None, obs.now())


def secure_sum_ints(values: Sequence[int], *, n_participants: int,
                    seed: int = 0) -> int:
    """Exact integer SecAgg sum — no float/fixed-point round-trip.

    Batch sizes (and any other small non-negative integer telemetry) embed
    directly into Z_2^32; the masked field sum is exact as long as the true
    total stays below 2^31 (it is validated).  This replaces the old route
    of ``frac_bits=0`` fixed-point encoding of ``float(size)``, which
    quantised through float64 for no reason.
    """
    values = [int(v) for v in values]
    if len(values) != n_participants:
        raise ValueError(
            f"secure_sum_ints: {len(values)} values for "
            f"{n_participants} participants — every participant must "
            "contribute"
        )
    if any(v < 0 for v in values):
        raise ValueError("secure_sum_ints: negative value")
    if sum(values) >= (1 << (_FIELD_BITS - 1)):
        raise ValueError("secure_sum_ints: total overflows the field")
    base_key = jax.random.key(seed)
    los, his = _pairs(n_participants)
    (masks,) = _signed_masks(n_participants, ((1,),), los, his,
                             base_key=base_key)
    masks = np.asarray(masks)[:, 0]
    with np.errstate(over="ignore"):  # modular field arithmetic
        ciphertexts = np.asarray(values, np.uint64).astype(_FIELD_DTYPE) + masks
        total = int(ciphertexts.sum(dtype=_FIELD_DTYPE))
    return total


# --------------------------------------------------------------------------
# Dropout-robust SecAgg: DH pairwise seeds + Shamir recovery (Bonawitz §4).
# --------------------------------------------------------------------------

# 2^61 - 1 (Mersenne prime).  One field for both the Shamir shares and the
# toy Diffie-Hellman group: large enough that pad seeds are unguessable in
# simulation, small enough that Python-int modexp stays negligible next to
# the gradient math.  A deployment would swap in X25519; the *protocol*
# (what is shared, who reveals what, when) is what we reproduce faithfully.
_SHAMIR_PRIME = (1 << 61) - 1
_DH_GENERATOR = 3


def shamir_share(
    secret: int, n_shares: int, threshold: int, rng: np.random.Generator
) -> list[tuple[int, int]]:
    """Split ``secret`` into n points of a degree-(threshold-1) polynomial."""
    if not 0 <= secret < _SHAMIR_PRIME:
        raise ValueError("secret out of field range")
    if not 1 <= threshold <= n_shares:
        raise ValueError("need 1 <= threshold <= n_shares")
    coeffs = [secret] + [
        int(rng.integers(0, _SHAMIR_PRIME)) for _ in range(threshold - 1)
    ]
    shares = []
    for x in range(1, n_shares + 1):
        y = 0
        for c in reversed(coeffs):  # Horner
            y = (y * x + c) % _SHAMIR_PRIME
        shares.append((x, y))
    return shares


def shamir_reconstruct(shares: Sequence[tuple[int, int]]) -> int:
    """Lagrange-interpolate the polynomial at 0 from >= threshold shares."""
    if not shares:
        raise ValueError("no shares to reconstruct from")
    if len({x for x, _ in shares}) != len(shares):
        raise ValueError("duplicate share indices")
    p = _SHAMIR_PRIME
    secret = 0
    for i, (xi, yi) in enumerate(shares):
        num, den = 1, 1
        for j, (xj, _) in enumerate(shares):
            if i == j:
                continue
            num = num * (-xj) % p
            den = den * (xi - xj) % p
        secret = (secret + yi * num * pow(den, p - 2, p)) % p
    return secret


class DropoutRobustSession:
    """SecAgg round that survives participants dropping before upload.

    Setup (simulated in-process; each step is one real protocol message):
      1. *advertise*: every participant i draws a DH secret u_i and
         publishes g^{u_i}.  The pairwise pad seed is the DH agreement
         s_ij = g^{u_i u_j} — unlike ``SecAggSession``'s shared base key,
         neither the facilitator nor any third party can derive it.
      2. *share keys*: i Shamir-shares u_i among all participants with a
         reconstruction ``threshold`` t (honest-majority default).

    On dropout of d (no upload received): any t survivors reveal their
    shares of u_d, the facilitator reconstructs u_d, recomputes the pads
    s_dj for every survivor j, and cancels them from the ciphertext sum.
    The result equals the plain sum of the *survivors'* values.

    Simplification vs. full Bonawitz: no self-masks (double masking), so a
    participant declared dropped *after* its upload was received would have
    its value exposed by unmasking.  We therefore never unmask received
    uploads — late-dropping participants simply stay in the sum (their
    contribution already arrived), matching the simulator's semantics.
    """

    def __init__(
        self,
        cfg: SecAggConfig,
        template: PyTree,
        *,
        threshold: int | None = None,
    ):
        n = cfg.n_participants
        if n < 2:
            raise ValueError("need at least 2 participants")
        self.cfg = cfg
        self.threshold = threshold if threshold is not None else n // 2 + 1
        if not 2 <= self.threshold <= n:
            raise ValueError(f"threshold {self.threshold} not in [2, {n}]")
        self.template = template
        _template_layout(self, template)
        self._los, self._his = _pairs(n)
        self._masks: list[jax.Array] | None = None  # built lazily
        # Each participant's local randomness (one stream per party would be
        # the deployment picture; a single seeded stream keeps tests exact).
        rng = np.random.default_rng(np.uint64(cfg.seed) ^ np.uint64(0x5ECA66))
        self._secret_keys = [
            int(rng.integers(2, _SHAMIR_PRIME - 1)) for _ in range(n)
        ]
        self.public_keys = [
            pow(_DH_GENERATOR, u, _SHAMIR_PRIME) for u in self._secret_keys
        ]
        # shares[i][j] = participant j's share of u_i (index x = j + 1)
        self._shares = [
            shamir_share(u, n, self.threshold, rng) for u in self._secret_keys
        ]

    # -- pads ---------------------------------------------------------------

    def _pair_seed(self, holder: int, other: int) -> int:
        """DH agreement: pow(pk_other, u_holder) == g^(u_i u_j), symmetric."""
        return pow(
            self.public_keys[other], self._secret_keys[holder], _SHAMIR_PRIME
        )

    def _net_masks(self) -> list[jax.Array]:
        """Every participant's net mask; each pair's pad generated once,
        accumulated over pair chunks (never the full pad matrix)."""
        if self._masks is None:
            seeds = [
                self._pair_seed(int(lo), int(hi))
                for lo, hi in zip(self._los, self._his)
            ]
            self._masks = _session_masks(self, seeds=seeds)
        return self._masks

    def upload(self, i: int, values: PyTree) -> list[jax.Array]:
        """Masked ciphertext from participant i (pads vs. every peer)."""
        return _masked_cohort_uploads(self, {i: values})[i]

    def upload_all(
        self, values: Mapping[int, PyTree]
    ) -> dict[int, list[jax.Array]]:
        """Ciphertexts for a whole cohort: one masking program on the
        device (slot index -> masked ciphertext)."""
        return _masked_cohort_uploads(self, values)

    # -- recovery -----------------------------------------------------------

    def recovery_shares(
        self, dropped: int, survivors: Sequence[int]
    ) -> list[tuple[int, int]]:
        """Shares of u_dropped that the survivors reveal to the facilitator."""
        return [self._shares[dropped][j] for j in survivors]

    def aggregate(
        self, uploads: dict[int, list[jax.Array]]
    ) -> PyTree:
        """Sum received ciphertexts; reconstruct + cancel dropped pads.

        ``uploads`` maps participant index -> ciphertext.  Participants
        absent from the dict are treated as dropped and recovered via
        Shamir.  Raises if fewer than ``threshold`` uploads survive.
        """
        n = self.cfg.n_participants
        survivors = sorted(uploads)
        if any(not 0 <= s < n for s in survivors):
            raise ValueError("upload index out of range")
        dropped = [d for d in range(n) if d not in uploads]
        if len(survivors) < self.threshold:
            raise ValueError(
                f"only {len(survivors)} uploads for threshold "
                f"{self.threshold}: cannot reconstruct dropped masks"
            )
        t0 = obs.now()
        received = [uploads[s] for s in survivors]
        _check_leaves(received, self._shapes, "upload")
        recovered = None
        if dropped:
            seeds, signs = [], []
            for d in dropped:
                # Any `threshold` survivors' shares reconstruct u_d exactly.
                shares = self.recovery_shares(d, survivors[: self.threshold])
                u_d = shamir_reconstruct(shares)
                # Regenerate every survivor-side pad involving d from the
                # reconstructed secret and cancel it: survivor j applied
                # +pad if j < d else -pad, so subtract for j < d and add
                # back for j > d.
                seeds += [pow(self.public_keys[j], u_d, _SHAMIR_PRIME)
                          for j in survivors]
                signs += [_MINUS_ONE if j < d else 1 for j in survivors]
            # one batched PRG call for every dropped party's pads
            pads = _batched_seed_pads(*_seed_words(seeds), self._shapes)
            recovered = (pads, np.asarray(signs, np.uint32))
        return _decoded_tree(self, _participant_field_sum, received,
                             recovered, t0)


def secure_sum_with_dropouts(
    values: Sequence[PyTree | None],
    cfg: SecAggConfig,
    *,
    threshold: int | None = None,
) -> PyTree:
    """Full dropout-robust round; ``None`` entries are dropped participants."""
    values = list(values)
    if len(values) != cfg.n_participants:
        raise ValueError(
            f"{len(values)} slots for {cfg.n_participants} participants"
        )
    template = next((v for v in values if v is not None), None)
    if template is None:
        raise ValueError("every participant dropped; nothing to aggregate")
    session = DropoutRobustSession(cfg, template, threshold=threshold)
    uploads = session.upload_all(
        {i: v for i, v in enumerate(values) if v is not None}
    )
    return session.aggregate(uploads)


def secagg_recovery_bytes(
    n_participants: int, n_dropped: int = 0
) -> dict[str, float]:
    """Wire-cost model for the dropout-robust extension.

    Setup: each participant broadcasts an 8 B public key and sends one 16 B
    Shamir share (8 B y + index) to each peer.  Recovery: each survivor
    reveals one share per dropped participant to the facilitator.
    """
    n, d = n_participants, n_dropped
    setup = n * 8.0 + n * (n - 1) * 16.0
    recovery = (n - d) * d * 16.0
    return {"setup_bytes": setup, "recovery_bytes": recovery}


def secagg_message_bytes(n_params: int, n_participants: int,
                         frac_bits: int = 16) -> dict[str, float]:
    """Communication-cost model for Supp. Table 1 (bytes per round).

    Per participant: one masked vector (4 B/elem in Z_2^32) plus the pairwise
    seed exchange (32 B per peer).  The aggregator receives all uploads.
    """
    per_participant = 4.0 * n_params + 32.0 * (n_participants - 1)
    aggregator = per_participant * n_participants
    plain = 4.0 * n_params
    return {
        "per_participant_bytes": per_participant,
        "aggregator_bytes": aggregator,
        "plain_per_participant_bytes": plain,
        "plain_aggregator_bytes": plain * n_participants,
    }
