"""Pallas TPU kernel: per-example ghost gradient norms.

Computes n_b = sum_{s,t} (a_s . a_t)(g_s . g_t) without materialising the
[B, d_in, d_out] per-example weight gradients (Opacus' approach) or the full
[B, S, S] Gram matrices (the jnp oracle).  The (s, t) plane is tiled into
VMEM blocks; both Grams for a tile are two MXU matmuls, and the elementwise
product reduces into a per-example scalar accumulated across the grid.

VMEM working set per step: 2·(bs·d_in + bt·d_in + bs·d_out + bt·d_out) floats
plus two (bs, bt) tiles — e.g. bs = bt = 128, d = 4096 -> ~4.2 MiB fp32.
Arithmetic intensity vs the oracle: the oracle writes two [B,S,S] Grams to
HBM (O(B S^2) bytes); the kernel keeps them in VMEM (never leaves the core).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ghost_norm_kernel(a_s_ref, a_t_ref, g_s_ref, g_t_ref, out_ref):
    s_idx = pl.program_id(1)
    t_idx = pl.program_id(2)

    @pl.when((s_idx == 0) & (t_idx == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a_s = a_s_ref[0].astype(jnp.float32)   # [bs, d_in]
    a_t = a_t_ref[0].astype(jnp.float32)   # [bt, d_in]
    g_s = g_s_ref[0].astype(jnp.float32)   # [bs, d_out]
    g_t = g_t_ref[0].astype(jnp.float32)   # [bt, d_out]
    aa = jax.lax.dot_general(a_s, a_t, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [bs, bt]
    gg = jax.lax.dot_general(g_s, g_t, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [bs, bt]
    # a (1, 1, 1) vector store: Mosaic refuses scalar stores into VMEM
    out_ref[...] += jnp.sum(aa * gg).reshape(1, 1, 1)


@functools.partial(jax.jit, static_argnames=("block_s", "block_t", "interpret"))
def ghost_norm_pallas(
    a: jax.Array,
    g: jax.Array,
    *,
    block_s: int = 128,
    block_t: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """a: [B, S, d_in]; g: [B, S, d_out] -> [B] float32 ghost norms^2."""
    b, s, d_in = a.shape
    _, _, d_out = g.shape
    block_s = min(block_s, s)
    block_t = min(block_t, s)
    if s % block_s or s % block_t:
        pad_s = (-s) % block_s if s % block_s else 0
        pad = max(pad_s, (-s) % block_t)
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        g = jnp.pad(g, ((0, 0), (0, pad), (0, 0)))
        s = a.shape[1]
    grid = (b, s // block_s, s // block_t)
    out = pl.pallas_call(
        _ghost_norm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_s, d_in), lambda b_, s_, t_: (b_, s_, 0)),
            pl.BlockSpec((1, block_t, d_in), lambda b_, s_, t_: (b_, t_, 0)),
            pl.BlockSpec((1, block_s, d_out), lambda b_, s_, t_: (b_, s_, 0)),
            pl.BlockSpec((1, block_t, d_out), lambda b_, s_, t_: (b_, t_, 0)),
        ],
        # the block's last two dims equal the array's (1, 1), as the TPU
        # tiling rule requires; a (1, 1) block over a (b, 1) array is refused
        out_specs=pl.BlockSpec((1, 1, 1), lambda b_, s_, t_: (b_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1, 1), jnp.float32),
        interpret=interpret,
    )(a, a, g, g)
    return out[:, 0, 0]
