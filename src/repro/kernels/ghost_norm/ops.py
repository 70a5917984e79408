"""Jit'd public wrapper for the ghost-norm kernel with CPU fallback."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.ghost_norm.kernel import ghost_norm_pallas
from repro.kernels.ghost_norm.ref import ghost_norm_ref


def ghost_norm_blocked(a: jax.Array, g: jax.Array,
                       block: int = 256) -> jax.Array:
    """The kernel's algorithm in plain XLA: scan over (s, t) tiles so the
    Gram working set stays [B, bs, bt] instead of [B, S, S].  Used on
    non-TPU backends (and in the dry-run, so compile-time memory matches the
    TPU kernel's VMEM behaviour rather than the naive oracle's)."""
    b, s, _ = a.shape
    block = min(block, s)
    if s % block != 0:
        pad = block - s % block
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        g = jnp.pad(g, ((0, 0), (0, pad), (0, 0)))
        s = a.shape[1]
    n = s // block
    a_t = a.reshape(b, n, block, a.shape[-1]).swapaxes(0, 1)
    g_t = g.reshape(b, n, block, g.shape[-1]).swapaxes(0, 1)

    def outer(acc, st):
        a_s, g_s = st  # [B, bs, d]

        def inner(acc2, tt):
            a_tt, g_tt = tt
            aa = jnp.einsum("bsd,btd->bst", a_s.astype(jnp.float32),
                            a_tt.astype(jnp.float32))
            gg = jnp.einsum("bsd,btd->bst", g_s.astype(jnp.float32),
                            g_tt.astype(jnp.float32))
            return acc2 + jnp.sum(aa * gg, axis=(1, 2)), None

        acc, _ = jax.lax.scan(inner, acc, (a_t, g_t))
        return acc, None

    out, _ = jax.lax.scan(outer, jnp.zeros((b,), jnp.float32), (a_t, g_t))
    return out


@functools.lru_cache(maxsize=None)
def _kernel(block_s: int, block_t: int, interpret: bool):
    """The Pallas kernel as the dispatcher calls it.

    Each example's norm is independent, so a ``vmap`` (the fused round's,
    over silos) folds into the kernel's own example axis.  Under a mesh
    (``jax.set_mesh``, as the ``shard`` backend stages its programs) the
    kernel runs in a ``shard_map`` on each device's slice of the examples:
    the compiler cannot partition a Mosaic kernel itself.
    """
    kernel = functools.partial(ghost_norm_pallas, block_s=block_s,
                               block_t=block_t, interpret=interpret)

    @jax.custom_batching.custom_vmap
    def call(a, g):
        mesh = jax.sharding.get_abstract_mesh()
        if mesh.empty:
            return kernel(a, g)
        spec = P(mesh.axis_names) if a.shape[0] % mesh.size == 0 else P()
        # check_vma off: the kernel's out_shape declares no varying axes
        return jax.shard_map(kernel, in_specs=(spec, spec), out_specs=spec,
                             check_vma=False)(a, g)

    @call.def_vmap
    def _fold(axis_size, in_batched, a, g):
        a, g = (x if batched else jnp.broadcast_to(x, (axis_size,) + x.shape)
                for x, batched in zip((a, g), in_batched))
        out = call(a.reshape((-1,) + a.shape[-2:]),
                   g.reshape((-1,) + g.shape[-2:]))
        return out.reshape(a.shape[:-2]), True

    return call


def ghost_norm(a: jax.Array, g: jax.Array, *, block_s: int = 128,
               block_t: int = 128, force_kernel: bool = False,
               prefer_oracle: bool = False) -> jax.Array:
    """Per-example ghost gradient sq-norms.

    TPU -> Pallas kernel; elsewhere -> the blocked XLA equivalent (same
    tiling, bounded memory); ``force_kernel`` runs interpret mode (tests).
    The naive ``[B, S, S]`` Gram oracle is opt-in via ``prefer_oracle``
    (debugging only): making it the short-sequence default meant the CPU
    path exercised a *different* memory profile than the kernel it stands
    in for, and its full-Gram materialisation dominates host memory exactly
    where the blocked path is cheapest.
    """
    backend = jax.default_backend()
    if backend == "tpu" or force_kernel:
        return _kernel(block_s, block_t, interpret=backend != "tpu")(a, g)
    if prefer_oracle and a.ndim == 3:
        return ghost_norm_ref(a, g)
    return ghost_norm_blocked(a, g)
