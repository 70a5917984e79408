"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ghost_norm.kernel import ghost_norm_pallas
from repro.kernels.ghost_norm.ref import ghost_norm_ref

KEY = jax.random.key(42)


def _rand(shape, dtype, k, scale=0.5):
    return (scale * jax.random.normal(jax.random.fold_in(KEY, k), shape)).astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,din,dout,bs,bt",
    [
        (2, 64, 32, 16, 32, 32),
        (1, 96, 48, 48, 32, 64),   # padding path (96 % 64 != 0)
        (3, 128, 64, 8, 128, 128),
        (2, 32, 16, 16, 64, 64),   # blocks larger than seq
    ],
)
def test_ghost_norm_sweep(b, s, din, dout, bs, bt, dtype):
    a = _rand((b, s, din), dtype, 1)
    g = _rand((b, s, dout), dtype, 2, scale=0.1)
    ref = ghost_norm_ref(a, g)
    out = ghost_norm_pallas(a, g, block_s=bs, block_t=bt, interpret=True)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,h,kv,d,causal,window",
    [
        (1, 128, 4, 2, 32, True, None),
        (2, 128, 4, 4, 64, True, 32),
        (1, 256, 8, 2, 32, False, None),
        (1, 128, 2, 1, 128, True, None),   # MQA
    ],
)
def test_flash_attention_sweep(b, s, h, kv, d, causal, window, dtype):
    q = _rand((b, s, h, d), dtype, 1)
    k = _rand((b, s, kv, d), dtype, 2)
    v = _rand((b, s, kv, d), dtype, 3, scale=1.0)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 block_q=64, block_k=64, interpret=True)
    tol = 3e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(ref, np.float32), np.asarray(out, np.float32),
        atol=tol, rtol=tol,
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,l,h,kv,d,index,window,bk",
    [
        (2, 256, 4, 2, 32, 100, None, 128),
        (1, 512, 8, 8, 64, 511, None, 256),
        (2, 256, 4, 1, 32, 200, 64, 64),
        (1, 1024, 4, 4, 128, 0, None, 512),   # first token
    ],
)
def test_decode_attention_sweep(b, l, h, kv, d, index, window, bk, dtype):
    q = _rand((b, 1, h, d), dtype, 1)
    k = _rand((b, l, kv, d), dtype, 2)
    v = _rand((b, l, kv, d), dtype, 3, scale=1.0)
    ref = decode_attention_ref(q, k, v, index, window=window)
    out = decode_attention_pallas(q, k, v, index, window=window,
                                  block_k=bk, interpret=True)
    tol = 3e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(ref, np.float32), np.asarray(out, np.float32),
        atol=tol, rtol=tol,
    )


@pytest.mark.parametrize(
    "b,s,din,dout,block",
    [
        (2, 96, 48, 16, 64),    # pad branch: 96 % 64 != 0
        (1, 100, 32, 48, 64),   # pad branch, non-square d_in != d_out
        (3, 130, 16, 8, 32),    # pad branch, multiple tiles before the pad
        (2, 64, 32, 16, 64),    # exact tiling (no pad) for contrast
    ],
)
def test_ghost_norm_dispatch_paths_agree(b, s, din, dout, block):
    """blocked == oracle == Pallas-interpret, including the pad branch.

    The blocked path's ``s % block != 0`` zero-padding and the Pallas
    kernel's own tile padding must both be invisible: zeros contribute
    nothing to the Gram products.
    """
    from repro.kernels.ghost_norm.ops import ghost_norm, ghost_norm_blocked

    a = _rand((b, s, din), jnp.float32, 11)
    g = _rand((b, s, dout), jnp.float32, 12, scale=0.1)
    oracle = ghost_norm_ref(a, g)
    blocked = ghost_norm_blocked(a, g, block=block)
    interp = ghost_norm_pallas(a, g, block_s=block, block_t=block,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(oracle),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(interp), np.asarray(oracle),
                               rtol=2e-5, atol=2e-5)
    # public dispatch: blocked is the CPU default now, the full-Gram oracle
    # is opt-in — both must agree with the oracle's numbers
    np.testing.assert_allclose(np.asarray(ghost_norm(a, g)),
                               np.asarray(oracle), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(ghost_norm(a, g, prefer_oracle=True)),
        np.asarray(oracle), rtol=1e-6, atol=1e-6)


def test_ghost_norm_kernel_folds_vmap_and_runs_under_a_mesh():
    """The dispatcher's kernel path: a vmap over silos folds into the
    kernel's example axis, and under a mesh it runs in a shard_map — both
    give the oracle's per-example norms."""
    from repro.kernels.ghost_norm.ops import ghost_norm
    from repro.launch.mesh import make_debug_mesh

    a = _rand((3, 2, 32, 16), jnp.float32, 21)
    g = _rand((3, 2, 32, 8), jnp.float32, 22, scale=0.1)
    oracle = jax.vmap(ghost_norm_ref)(a, g)

    def kernel(x, y):
        return ghost_norm(x, y, block_s=16, block_t=16, force_kernel=True)

    folded = jax.jit(jax.vmap(kernel))(a, g)
    np.testing.assert_allclose(np.asarray(folded), np.asarray(oracle),
                               rtol=2e-5, atol=2e-5)
    shared_g = jax.vmap(kernel, in_axes=(0, None))(a, g[0])
    np.testing.assert_allclose(
        np.asarray(shared_g), np.asarray(jax.vmap(ghost_norm_ref, (0, None))(
            a, g[0])), rtol=2e-5, atol=2e-5)
    with jax.set_mesh(make_debug_mesh(1, 1)):
        on_mesh = jax.jit(jax.vmap(kernel))(a, g)
    np.testing.assert_allclose(np.asarray(on_mesh), np.asarray(oracle),
                               rtol=2e-5, atol=2e-5)


def test_ghost_norm_matches_outer_product_norms():
    """Cross-check vs literally materialised per-example weight grads."""
    b, s, din, dout = 3, 16, 8, 5
    a = _rand((b, s, din), jnp.float32, 7)
    g = _rand((b, s, dout), jnp.float32, 8)
    explicit = jnp.stack([
        jnp.sum(jnp.square(a[i].T @ g[i])) for i in range(b)
    ])
    out = ghost_norm_pallas(a, g, block_s=8, block_t=8, interpret=True)
    np.testing.assert_allclose(np.asarray(explicit), np.asarray(out), rtol=1e-5)


def test_flash_matches_model_attention_path():
    """Kernel output agrees with the model's einsum attention (GQA)."""
    from repro.models.attention import _sdpa, _causal_mask

    b, s, h, kv, d = 2, 128, 4, 2, 32
    q = _rand((b, s, h, d), jnp.float32, 1)
    k = _rand((b, s, kv, d), jnp.float32, 2)
    v = _rand((b, s, kv, d), jnp.float32, 3)
    model_out = _sdpa(q, k, v, _causal_mask(s, s))
    kernel_out = flash_attention_pallas(q, k, v, causal=True,
                                        block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(model_out), np.asarray(kernel_out),
                               atol=3e-5)
