"""Vectorized SecAgg vs the frozen per-leaf loop reference.

The vectorized path (one batched PRG call per round, flat field vectors,
sign-convention scatter) changes every pad *value* but not a single
aggregate *bit*: mask cancellation is exact in Z_2^32 either way, so the
sum of uploads equals the sum of encoded plaintexts exactly in both
implementations.  These tests pin that contract against the vendored
pre-refactor loops in ``tests/_legacy_secagg.py``, the device field
encode/decode bit for bit against that file's float64 NumPy
``_encode``/``_decode``, field round-trip properties, and the exact
integer sum path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core.secagg import (
    DropoutRobustSession,
    SecAggConfig,
    SecAggSession,
    secure_sum,
    secure_sum_ints,
    secure_sum_stack,
    secure_sum_with_dropouts,
    _field_decode,
    _field_encode,
)

from _legacy_secagg import (
    LegacySecAggSession,
    _decode,
    _encode,
    legacy_secure_sum,
    legacy_secure_sum_with_dropouts,
)

_device_encode = jax.jit(_field_encode, static_argnums=(1,))
_device_decode = jax.jit(_field_decode, static_argnums=(1,))


def _round_trip(x, cfg):
    """decode(encode(x)) through the device programs' field arithmetic."""
    enc = _device_encode(jnp.asarray(x, jnp.float32), cfg.frac_bits)
    return np.asarray(_device_decode(enc, cfg.frac_bits))


def _trees(rng, n, dims=(7, 3)):
    return [
        {"w": jnp.asarray(rng.normal(0, 3, dims[0]).astype(np.float32)),
         "b": {"c": jnp.asarray(rng.normal(0, 1, dims[1]).astype(np.float32))}}
        for _ in range(n)
    ]


# -- masks cancel + aggregates bit-identical to the legacy loops -------------


@settings(max_examples=15, deadline=None)
@given(n=st.integers(2, 6), dim=st.integers(1, 17), seed=st.integers(0, 500))
def test_vectorized_masks_cancel_exactly(n, dim, seed):
    cfg = SecAggConfig(n, frac_bits=16, seed=seed)
    session = SecAggSession(cfg, {"w": jnp.zeros((dim,))})
    with np.errstate(over="ignore"):
        total = sum(
            np.asarray(session.mask_for(i)[0], dtype=np.uint64)
            for i in range(n)
        ) % (1 << 32)
    assert (total == 0).all()


@settings(max_examples=10, deadline=None)
@given(n=st.integers(2, 6), seed=st.integers(0, 500))
def test_secure_sum_bit_identical_to_legacy_loop(n, seed):
    rng = np.random.default_rng(seed)
    vals = _trees(rng, n)
    cfg = SecAggConfig(n, frac_bits=16, seed=seed)
    new = secure_sum(vals, cfg)
    old = legacy_secure_sum(vals, cfg)
    for a, b in zip(jax.tree_util.tree_leaves(new),
                    jax.tree_util.tree_leaves(old)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("n", [2, 5])
def test_secure_sum_stack_bit_identical_to_legacy_loop(n):
    """The fused round's entry point: the cohort's payloads as one (n, ...)
    stack per leaf, masked, summed and decoded where they lie."""
    rng = np.random.default_rng(n)
    vals = _trees(rng, n)
    cfg = SecAggConfig(n, frac_bits=16, seed=n)
    stack = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *vals)
    new = secure_sum_stack(stack, cfg)
    old = legacy_secure_sum(vals, cfg)
    for a, b in zip(jax.tree_util.tree_leaves(new),
                    jax.tree_util.tree_leaves(old)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="leading axis"):
        secure_sum_stack(stack, SecAggConfig(n + 1, seed=0))


def test_empty_and_scalar_leaves_round_trip():
    """Zero-size leaves contribute 0 field elements and scalars 1 — the
    flat vector and the mask rows must agree on both."""
    tree = {"w": jnp.zeros((0,)), "b": jnp.asarray(1.25),
            "v": jnp.asarray([0.5, -0.5])}
    out = secure_sum([tree, tree, tree], SecAggConfig(3, seed=5))
    assert np.shape(out["w"]) == (0,)
    np.testing.assert_allclose(float(out["b"]), 3.75, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out["v"]), [1.5, -1.5], atol=1e-4)
    out2 = secure_sum_with_dropouts(
        [tree, tree, None], SecAggConfig(3, seed=5), threshold=2
    )
    np.testing.assert_allclose(float(out2["b"]), 2.5, atol=1e-4)


def test_ciphertexts_differ_but_sums_agree():
    """The pads changed (one generation per pair, flat derivation) — a
    sanity check that this test file isn't comparing identical bytes."""
    cfg = SecAggConfig(3, frac_bits=16, seed=11)
    tmpl = {"w": jnp.zeros((16,))}
    x = {"w": jnp.ones((16,))}
    new_up = SecAggSession(cfg, tmpl).upload(0, x)[0]
    old_up = LegacySecAggSession(cfg, tmpl).upload(0, x)[0]
    assert not np.array_equal(new_up, old_up)


@pytest.mark.parametrize("dropped", [set(), {2}, {0, 4}, {1, 2}])
def test_dropout_aggregate_bit_identical_to_legacy_loop(dropped):
    rng = np.random.default_rng(3)
    n = 5
    vals = _trees(rng, n)
    cfg = SecAggConfig(n, frac_bits=16, seed=7)
    slots = [None if i in dropped else vals[i] for i in range(n)]
    new = secure_sum_with_dropouts(slots, cfg, threshold=3)
    old = legacy_secure_sum_with_dropouts(slots, cfg, threshold=3)
    for a, b in zip(jax.tree_util.tree_leaves(new),
                    jax.tree_util.tree_leaves(old)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dropout_recovery_pads_regenerate_from_secret():
    """Upload-side pads and recovery-side pads must come from the same
    seed-keyed derivation: with a dropout, the recovered aggregate equals
    the survivors' plain sum to fixed-point exactness."""
    rng = np.random.default_rng(4)
    n = 4
    vals = _trees(rng, n)
    cfg = SecAggConfig(n, frac_bits=16, seed=9)
    session = DropoutRobustSession(cfg, vals[0], threshold=2)
    uploads = {i: session.upload(i, vals[i]) for i in range(n) if i != 1}
    out = session.aggregate(uploads)
    expected = sum(
        np.concatenate([np.asarray(v["w"]), np.asarray(v["b"]["c"])])
        for i, v in enumerate(vals) if i != 1
    )
    got = np.concatenate([np.asarray(out["w"]), np.asarray(out["b"]["c"])])
    np.testing.assert_allclose(got, expected, atol=n * 2**-15)


# -- device field encode/decode == the float64 NumPy reference, bit for bit --


def _f32_bits(bits):
    return np.asarray(bits, np.uint32).view(np.float32)


def _encode_inputs(kind: str) -> np.ndarray:
    """float32 inputs of one class, both signs."""
    rng = np.random.default_rng(17)
    if kind == "normal":
        mag = np.exp2(rng.uniform(-30.0, 14.0, 20_000))
        x = np.concatenate([rng.normal(0.0, 3.0, 20_000), mag])
    elif kind == "ties":  # (k + 0.5) / 2^16: exactly half a grid step
        k = rng.integers(-(1 << 22), 1 << 22, 20_000)
        x = np.concatenate([(k + 0.5) / 65536.0,
                            (np.arange(-64, 64) + 0.5) / 65536.0])
    elif kind == "zeros":  # +-0 and what rounds to it (ties to even)
        x = np.asarray([0.0, -0.0, 2.0**-17, -2.0**-17, 2.0**-18,
                        3 * 2.0**-18, 2.0**-40, -2.0**-100])
    elif kind == "subnormal":
        bits = np.concatenate([np.arange(1, 4096),
                               rng.integers(1, 1 << 23, 10_000)])
        x = _f32_bits(bits)
    elif kind == "wrap":  # |x * 2^16| at and past 2^31 (and 2^32)
        edges = np.float32([2.0**15, 2.0**16, 2.0**15 + 2.0**14])
        x = np.concatenate([
            edges, np.nextafter(edges, np.float32(0)),
            np.nextafter(edges, np.float32(np.inf)),
            rng.uniform(2.0**14, 2.0**17, 20_000),
        ])
    else:  # "huge": magnitudes up to 2^46
        x = np.exp2(rng.uniform(15.0, 46.0, 20_000))
        x = np.concatenate([x, np.float32([2.0**46, 2.0**45 + 2.0**30])])
    x = np.asarray(x, np.float32)
    return np.concatenate([x, -x])


def _decode_inputs(kind: str) -> np.ndarray:
    """uint32 field words of one bit-pattern class."""
    rng = np.random.default_rng(23)
    top = 1 << 32
    if kind == "edges":
        v = [0, 1, 2, 3, (1 << 24) - 1, 1 << 24, (1 << 24) + 1,
             (1 << 31) - 1, 1 << 31, (1 << 31) + 1, top - (1 << 24) - 1,
             top - 2, top - 1]
    elif kind == "near_zero":  # small magnitudes of both signs
        v = np.concatenate([np.arange(0, 1 << 16),
                            np.arange(top - (1 << 16), top)])
    elif kind == "rounded":  # |v| past 2^24: float32 rounds, ties to even
        k = rng.integers(1 << 23, 1 << 24, 10_000)
        tie = 4 * k + 2  # halfway between float32 neighbours in [2^25, 2^26)
        big = rng.integers(1 << 24, 1 << 31, 10_000)
        v = np.concatenate([tie, top - tie, big, top - big])
    else:  # "uniform": every bit pattern equally likely
        v = rng.integers(0, top, 40_000)
    return np.asarray(v, np.uint64).astype(np.uint32)


@pytest.mark.parametrize(
    "kind", ["normal", "ties", "zeros", "subnormal", "wrap", "huge"])
def test_device_encode_bit_identical_to_numpy(kind):
    x = _encode_inputs(kind)
    cfg = SecAggConfig(2, frac_bits=16)
    got = np.asarray(_device_encode(jnp.asarray(x), cfg.frac_bits))
    np.testing.assert_array_equal(got, _encode(x, cfg))


@pytest.mark.parametrize("kind", ["edges", "near_zero", "rounded", "uniform"])
@pytest.mark.parametrize("frac_bits", [0, 16])
def test_device_decode_bit_identical_to_numpy(kind, frac_bits):
    v = _decode_inputs(kind)
    cfg = SecAggConfig(2, frac_bits=frac_bits)
    got = np.asarray(_device_decode(jnp.asarray(v), cfg.frac_bits))
    np.testing.assert_array_equal(got.view(np.uint32),
                                  _decode(v, cfg).view(np.uint32))


# -- field encode/decode round-trips -----------------------------------------


@settings(max_examples=50, deadline=None)
@given(
    x=st.floats(min_value=-30000.0, max_value=30000.0,
                allow_nan=False, allow_infinity=False),
    frac_bits=st.integers(0, 20),
)
def test_encode_decode_round_trip(x, frac_bits):
    """decode(encode(x)) is x rounded to the fixed-point grid (exactly),
    for every value whose quantisation fits the field's signed half."""
    cfg = SecAggConfig(2, frac_bits=frac_bits)
    q = np.round(np.float64(np.float32(x)) * cfg.scale)
    if abs(q) >= 2**31:
        return  # out of field range: wraps by design
    got = _round_trip(np.float32(x), cfg)
    want = np.float32(q / cfg.scale)
    assert got == want


@settings(max_examples=30, deadline=None)
@given(v=st.integers(0, 2**24), frac_bits=st.integers(0, 6))
def test_integers_on_the_grid_survive_exactly(v, frac_bits):
    """Integers round-trip the float fixed-point path exactly only up to
    float32's 2^24 mantissa limit — the reason ``secure_sum_ints`` exists:
    the field itself is exact to 2^31, the float decode is not."""
    cfg = SecAggConfig(2, frac_bits=frac_bits)
    if v * cfg.scale >= 2**31:  # representable range shrinks with frac bits
        return
    assert float(_round_trip(float(v), cfg)) == float(v)


def test_float_path_loses_big_integers_but_int_path_does_not():
    """Above 2^24 the old float round-trip quantises; the integer field
    sum stays exact — the sum_sizes bugfix, demonstrated."""
    v = 366_390_673  # < 2^31, not representable in float32
    cfg = SecAggConfig(2, frac_bits=0)
    assert float(_round_trip(float(v), cfg)) != float(v)
    assert secure_sum_ints([v, 17], n_participants=2, seed=0) == v + 17


# -- exact integer sums (the sum_sizes bugfix) -------------------------------


@settings(max_examples=30, deadline=None)
@given(
    sizes=st.lists(st.integers(0, 1 << 27), min_size=1, max_size=12),
    seed=st.integers(0, 100),
)
def test_secure_sum_ints_is_exact(sizes, seed):
    got = secure_sum_ints(sizes, n_participants=len(sizes), seed=seed)
    assert got == sum(sizes)


def test_secure_sum_ints_validates():
    with pytest.raises(ValueError, match="participants"):
        secure_sum_ints([1, 2], n_participants=3)
    with pytest.raises(ValueError, match="negative"):
        secure_sum_ints([-1], n_participants=1)
    with pytest.raises(ValueError, match="overflow"):
        secure_sum_ints([1 << 31], n_participants=1)
    assert secure_sum_ints([5], n_participants=1, seed=3) == 5  # no pairs
