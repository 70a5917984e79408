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
from functools import partial
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import repro.obs as obs

PyTree = Any

_FIELD_DTYPE = np.uint32
_FIELD_BITS = 32

# Field arithmetic runs host-side in NumPy: the protocol is a host/network
# concern (uploads are ciphertexts, not device tensors) and NumPy gives exact
# 64->32-bit modular arithmetic regardless of jax_enable_x64.


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


def _encode(x, cfg: SecAggConfig) -> np.ndarray:
    """float -> field element (two's-complement embedding into uint32)."""
    q = np.round(np.asarray(x, np.float64) * cfg.scale).astype(np.int64)
    return (q % (1 << _FIELD_BITS)).astype(_FIELD_DTYPE)


def _decode(v: np.ndarray, cfg: SecAggConfig) -> np.ndarray:
    """field element -> float (centered: values >= 2^31 are negative)."""
    v = v.astype(np.int64)
    v = np.where(v >= (1 << (_FIELD_BITS - 1)), v - (1 << _FIELD_BITS), v)
    return (v.astype(np.float64) / cfg.scale).astype(np.float32)


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


def _pairs(n: int) -> tuple[np.ndarray, np.ndarray]:
    """Index arrays (los, his) over the n*(n-1)/2 unordered pairs, lo < hi."""
    lo, hi = np.triu_indices(n, k=1)
    return lo.astype(np.uint32), hi.astype(np.uint32)


@partial(jax.jit, static_argnums=(4, 5))
def _pair_mask_scan(
    base_key: jax.Array, los: jax.Array, his: jax.Array, valid: jax.Array,
    n: int, length: int,
) -> jax.Array:
    """(n, L) signed net masks from (n_chunks, C) pair-index chunks."""

    def body(masks, inp):
        lo_c, hi_c, v_c = inp

        def one(lo, hi):
            k = jax.random.fold_in(jax.random.fold_in(base_key, lo), hi)
            return jax.random.bits(k, (length,), dtype=jnp.uint32)

        pads = jax.vmap(one)(lo_c, hi_c) * v_c[:, None]  # pad rows -> 0
        masks = masks.at[lo_c].add(pads)
        masks = masks.at[hi_c].add(-pads)  # uint32: exact two's complement
        return masks, None

    masks0 = jnp.zeros((n, length), jnp.uint32)
    masks, _ = jax.lax.scan(body, masks0, (los, his, valid))
    return masks


_SEED_PAD_KEY = jax.random.key(0x5ECA66)


def _seed_pad_row(hi, lo, length: int):
    """One pad row from a DH agreement split into 32-bit words.

    This is THE derivation: the masking scan and the dropout-recovery path
    must produce bit-identical pads from the same seed words, or survivors'
    regenerated pads no longer cancel a dropped party's masks — so both go
    through this one function.
    """
    k = jax.random.fold_in(jax.random.fold_in(_SEED_PAD_KEY, hi), lo)
    return jax.random.bits(k, (length,), dtype=jnp.uint32)


@partial(jax.jit, static_argnums=(5, 6))
def _seed_mask_scan(
    hi_words: jax.Array, lo_words: jax.Array,
    los: jax.Array, his: jax.Array, valid: jax.Array,
    n: int, length: int,
) -> jax.Array:
    """(n, L) signed net masks from chunked DH-seed words (the seed, not
    the pair indices, keys the PRG — so a pad can be regenerated from a
    Shamir-reconstructed secret during recovery)."""

    def body(masks, inp):
        hw_c, lw_c, lo_c, hi_c, v_c = inp
        pads = jax.vmap(
            lambda hi, lo: _seed_pad_row(hi, lo, length)
        )(hw_c, lw_c) * v_c[:, None]
        masks = masks.at[lo_c].add(pads)
        masks = masks.at[hi_c].add(-pads)
        return masks, None

    masks0 = jnp.zeros((n, length), jnp.uint32)
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
    length: int,
    los: np.ndarray,
    his: np.ndarray,
    *,
    chunk: int = _DEFAULT_PAD_CHUNK,
    base_key: jax.Array | None = None,
    seeds: Sequence[int] | None = None,
) -> np.ndarray:
    """(n, L) net masks: row i = sum_{i=lo} pad - sum_{i=hi} pad (mod 2^32),
    accumulated in-jit over ``chunk``-pair slices; exactly one of
    ``base_key`` (pair-index keyed pads) / ``seeds`` (DH-agreement keyed
    pads) selects the PRG family."""
    if len(los) == 0:
        return np.zeros((n, length), _FIELD_DTYPE)
    if seeds is not None:
        hi_w, lo_w = _seed_words(seeds)
        hw, lw, lo_c, hi_c, valid = _chunked([hi_w, lo_w, los, his], chunk)
        out = _seed_mask_scan(hw, lw, lo_c, hi_c, valid, n, length)
    else:
        lo_c, hi_c, valid = _chunked([los, his], chunk)
        out = _pair_mask_scan(base_key, lo_c, hi_c, valid, n, length)
    return np.asarray(out)


def _seed_words(seeds: Sequence[int]) -> tuple[np.ndarray, np.ndarray]:
    arr = [int(s) for s in seeds]
    hi = np.asarray([s >> 32 for s in arr], np.uint32)
    lo = np.asarray([s & 0xFFFFFFFF for s in arr], np.uint32)
    return hi, lo


@partial(jax.jit, static_argnums=(2,))
def _batched_seed_pads(
    hi_words: jax.Array, lo_words: jax.Array, length: int
) -> jax.Array:
    """(n_seeds, length) pads from DH-seed words — the *recovery* path,
    where the handful of survivor-side pads of a dropped party really is
    needed as a matrix (to cancel them from the ciphertext sum).  Same
    ``_seed_pad_row`` derivation as the masking scan, by construction."""
    return jax.vmap(
        lambda hi, lo: _seed_pad_row(hi, lo, length)
    )(hi_words, lo_words)


def _flatten_encoded(
    leaves: Sequence[Any], template: Sequence[Any], cfg: SecAggConfig
) -> np.ndarray:
    """Encode every leaf and concatenate into one flat field vector."""
    out = []
    for li, (x, tmpl) in enumerate(zip(leaves, template)):
        shape = tuple(np.shape(tmpl))
        if tuple(np.shape(x)) != shape:
            raise ValueError(f"leaf {li} shape {np.shape(x)} != {shape}")
        out.append(_encode(x, cfg).ravel())
    return np.concatenate(out) if out else np.zeros((0,), _FIELD_DTYPE)


def _encode_cohort(
    trees: Sequence[PyTree], template: Sequence[Any], cfg: SecAggConfig
) -> np.ndarray:
    """(n, L) encoded field matrix for a whole cohort of payload trees.

    ONE ``jax.device_get`` moves every participant's (possibly
    device-resident) payload leaves to the host together, instead of the
    per-silo implicit transfers the per-upload ``np.asarray`` path paid;
    the fixed-point encode is elementwise, so batching changes no bit.
    """
    cohort_leaves = [jax.tree_util.tree_leaves(v) for v in trees]
    for leaves in cohort_leaves:
        if len(leaves) != len(template):
            raise ValueError("pytree structure mismatch")
    with obs.span("secagg.encode", cat="secagg"):
        cohort_leaves = jax.device_get(cohort_leaves)
        return np.stack([
            _flatten_encoded(leaves, template, cfg)
            for leaves in cohort_leaves
        ]) if cohort_leaves else np.zeros((0, 0), _FIELD_DTYPE)


def _split_flat(flat: np.ndarray, template: Sequence[Any]) -> list[np.ndarray]:
    """Inverse of ``_flatten_encoded``: flat vector -> per-leaf arrays."""
    out, off = [], 0
    for leaf in template:
        shape = tuple(np.shape(leaf))
        # np.prod(()) == 1, so scalars count 1 and empty leaves count 0 —
        # matching exactly what _flatten_encoded ravels
        size = int(np.prod(shape))
        out.append(flat[off:off + size].reshape(shape))
        off += size
    return out


def _stack_ciphertexts(
    uploads: Sequence[list[np.ndarray]],
) -> np.ndarray:
    """(n_uploads, L) field matrix from per-leaf ciphertext lists."""
    return np.stack([
        np.concatenate([np.asarray(u).ravel() for u in up])
        for up in uploads
    ])


def _masked_cohort_uploads(
    session, values: Mapping[int, PyTree]
) -> dict[int, list[np.ndarray]]:
    """Shared ``upload_all`` body: one batched host transfer for the whole
    cohort's payloads + one vectorized masking pass.  Bit-identical to
    per-participant ``upload`` calls (encode is elementwise, masks are the
    same rows)."""
    if not values:
        return {}
    order = sorted(values)
    with obs.span("secagg.upload", cat="secagg", participants=len(order),
                  length=session._length):
        enc = _encode_cohort(
            [values[i] for i in order], session._leaves, session.cfg
        )
        with np.errstate(over="ignore"):  # modular field arithmetic
            enc = enc + session._flat_masks()[np.asarray(order, np.intp)]
        return {
            i: _split_flat(row, session._leaves)
            for i, row in zip(order, enc)
        }


def _session_masks(session, **prg) -> np.ndarray:
    """The session's (n, L) net-mask matrix under a ``secagg.masks`` span:
    the pad program on the device and the fetch of its result.  ``prg`` is
    ``_signed_masks``'s ``base_key`` or ``seeds``."""
    t0 = obs.now()
    masks = _signed_masks(
        session.cfg.n_participants, session._length,
        session._los, session._his,
        chunk=session.cfg.pad_chunk_pairs, **prg,
    )
    if t0 is not None:
        obs.complete("secagg.masks", t0, cat="secagg",
                     pairs=len(session._los), bytes=masks.nbytes)
    return masks


def _decoded_tree(session, total: np.ndarray, t0: float | None) -> PyTree:
    """Decode the field sum back onto the device as the template's pytree,
    and close the ``secagg.sum`` span opened at ``t0``."""
    decoded = [
        jnp.asarray(_decode(t, session.cfg))
        for t in _split_flat(total, session._leaves)
    ]
    if t0 is not None:
        obs.complete("secagg.sum", t0, cat="secagg",
                     bytes=sum(x.nbytes for x in decoded))
    return jax.tree_util.tree_unflatten(session._treedef, decoded)


class SecAggSession:
    """One aggregation round over a fixed pytree template."""

    def __init__(self, cfg: SecAggConfig, template: PyTree):
        self.cfg = cfg
        self.template = template
        self._leaves, self._treedef = jax.tree_util.tree_flatten(template)
        self._length = int(sum(
            np.prod(np.shape(x)) for x in self._leaves
        ))
        self._base_key = jax.random.key(cfg.seed)
        self._los, self._his = _pairs(cfg.n_participants)
        self._masks: np.ndarray | None = None  # (n, L), built lazily

    def _flat_masks(self) -> np.ndarray:
        """Every participant's net mask, accumulated over pair chunks."""
        if self._masks is None:
            self._masks = _session_masks(self, base_key=self._base_key)
        return self._masks

    def mask_for(self, i: int) -> list[np.ndarray]:
        """Net mask participant i applies (sums to zero over participants)."""
        return _split_flat(self._flat_masks()[i], self._leaves)

    def upload(self, i: int, values: PyTree) -> list[np.ndarray]:
        """Masked ciphertext participant i sends to the leader."""
        leaves = jax.tree_util.tree_leaves(values)
        if len(leaves) != len(self._leaves):
            raise ValueError("pytree structure mismatch")
        with np.errstate(over="ignore"):  # modular wraparound is the protocol
            flat = _flatten_encoded(leaves, self._leaves, self.cfg)
            flat = flat + self._flat_masks()[i]
        return _split_flat(flat, self._leaves)

    def upload_all(
        self, values: Mapping[int, PyTree]
    ) -> dict[int, list[np.ndarray]]:
        """Ciphertexts for a whole cohort: one host transfer, one masking
        pass (participant index -> masked ciphertext)."""
        return _masked_cohort_uploads(self, values)

    def aggregate(self, uploads: Sequence[list[np.ndarray]]) -> PyTree:
        """Leader-side sum of ciphertexts; masks cancel exactly in Z_2^32."""
        if len(uploads) != self.cfg.n_participants:
            raise ValueError(
                "honest-but-curious SecAgg requires all participants "
                f"({len(uploads)} of {self.cfg.n_participants} uploads); a "
                "missing upload leaves un-cancelled masks in the sum — use "
                "DropoutRobustSession if participants may drop out"
            )
        t0 = obs.now()
        _check_uploads(uploads, self._leaves)
        with np.errstate(over="ignore"):  # modular wraparound is the protocol
            total = _stack_ciphertexts(uploads).sum(
                axis=0, dtype=_FIELD_DTYPE
            )
        return _decoded_tree(self, total, t0)


def _check_uploads(
    uploads: Sequence[list[np.ndarray]], leaves: Sequence[Any]
) -> None:
    """Fail loudly on short/misshapen ciphertexts (silent-garbage guard)."""
    for k, up in enumerate(uploads):
        if len(up) != len(leaves):
            raise ValueError(
                f"upload {k} has {len(up)} leaves, template has "
                f"{len(leaves)} — truncated or mis-structured ciphertext"
            )
        for li, (u, leaf) in enumerate(zip(up, leaves)):
            if tuple(np.shape(u)) != tuple(np.shape(leaf)):
                raise ValueError(
                    f"upload {k} leaf {li} shape {np.shape(u)} != template "
                    f"shape {np.shape(leaf)}"
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
    masks = _signed_masks(n_participants, 1, los, his,
                          base_key=base_key)[:, 0]
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
        self._leaves, self._treedef = jax.tree_util.tree_flatten(template)
        self._length = int(sum(
            np.prod(np.shape(x)) for x in self._leaves
        ))
        self._los, self._his = _pairs(n)
        self._masks: np.ndarray | None = None  # (n, L), built lazily
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

    def _pads_from_seeds(self, seeds: Sequence[int]) -> np.ndarray:
        """(len(seeds), L) pads from DH agreements, one batched PRG call."""
        if not seeds:
            return np.zeros((0, self._length), _FIELD_DTYPE)
        hi, lo = _seed_words(seeds)
        return np.asarray(_batched_seed_pads(hi, lo, self._length))

    def _flat_masks(self) -> np.ndarray:
        """Every participant's net mask; each pair's pad generated once,
        accumulated over pair chunks (never the full pad matrix)."""
        if self._masks is None:
            seeds = [
                self._pair_seed(int(lo), int(hi))
                for lo, hi in zip(self._los, self._his)
            ]
            self._masks = _session_masks(self, seeds=seeds)
        return self._masks

    def upload(self, i: int, values: PyTree) -> list[np.ndarray]:
        """Masked ciphertext from participant i (pads vs. every peer)."""
        leaves = jax.tree_util.tree_leaves(values)
        if len(leaves) != len(self._leaves):
            raise ValueError("pytree structure mismatch")
        with np.errstate(over="ignore"):  # modular field arithmetic
            flat = _flatten_encoded(leaves, self._leaves, self.cfg)
            flat = flat + self._flat_masks()[i]
        return _split_flat(flat, self._leaves)

    def upload_all(
        self, values: Mapping[int, PyTree]
    ) -> dict[int, list[np.ndarray]]:
        """Ciphertexts for a whole cohort: one host transfer, one masking
        pass (slot index -> masked ciphertext)."""
        return _masked_cohort_uploads(self, values)

    # -- recovery -----------------------------------------------------------

    def recovery_shares(
        self, dropped: int, survivors: Sequence[int]
    ) -> list[tuple[int, int]]:
        """Shares of u_dropped that the survivors reveal to the facilitator."""
        return [self._shares[dropped][j] for j in survivors]

    def aggregate(
        self, uploads: dict[int, list[np.ndarray]]
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
        _check_uploads([uploads[s] for s in survivors], self._leaves)
        with np.errstate(over="ignore"):
            total = _stack_ciphertexts(
                [uploads[s] for s in survivors]
            ).sum(axis=0, dtype=_FIELD_DTYPE)
            for d in dropped:
                # Any `threshold` survivors' shares reconstruct u_d exactly.
                shares = self.recovery_shares(d, survivors[: self.threshold])
                u_d = shamir_reconstruct(shares)
                # Regenerate every survivor-side pad involving d from the
                # reconstructed secret (one batched PRG call per dropped
                # party) and cancel: survivor j applied +pad if j < d else
                # -pad, so subtract for j < d and add back for j > d.
                pads = self._pads_from_seeds([
                    pow(self.public_keys[j], u_d, _SHAMIR_PRIME)
                    for j in survivors
                ])
                before = np.asarray([j < d for j in survivors])
                total = total - pads[before].sum(axis=0, dtype=_FIELD_DTYPE)
                total = total + pads[~before].sum(axis=0, dtype=_FIELD_DTYPE)
        return _decoded_tree(self, total, t0)


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
