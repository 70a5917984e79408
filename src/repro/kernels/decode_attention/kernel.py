"""Pallas TPU kernel: single-query attention against a long KV cache.

The decode hot-spot for the 32k/500k serving shapes: one query row per
(batch, head) streamed against KV blocks with online-softmax running stats in
VMEM.  The KV length is the innermost grid dimension so the cache streams
HBM->VMEM exactly once; positions beyond ``index`` (and outside the sliding
window) are masked with the current-position scalar prefetched via
PrefetchScalarGridSpec.

GQA is expressed in the index map (KV head = h // group) — the cache is
never expanded.  Block = (bk, d): bk = 512, d = 128 -> 0.5 MiB fp32 per K/V
step, well under VMEM, and the dominant HBM term is the unavoidable one
(reading the cache once).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(index_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, block_k: int, window,
                   kv_steps: int):
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    index = index_ref[0]
    q = q_ref[0, 0].astype(jnp.float32) * scale          # [1, d]
    k = k_ref[0, 0].astype(jnp.float32)                  # [bk, d]
    v = v_ref[0, 0].astype(jnp.float32)                  # [bk, d]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [1, bk]
    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    ok = k_pos <= index
    if window is not None:
        ok &= k_pos > index - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new

    @pl.when(kj == kv_steps - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


# KV-cache tile: a cache longer than this must be a whole number of tiles
DEFAULT_BLOCK_K = 512


@functools.partial(jax.jit, static_argnames=("window", "block_k", "interpret"))
def decode_attention_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array, index: jax.Array, *,
    window: int | None = None, block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """q: [B,1,H,D]; k,v: [B,L,KV,D]; index: scalar -> [B,1,H,D]."""
    b, _, h, d = q.shape
    l, kv = k.shape[1], k.shape[2]
    group = h // kv
    block_k = min(block_k, l)
    assert l % block_k == 0, "cache length must divide block_k"
    qh = q.reshape(b, h, 1, d)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    kv_steps = l // block_k
    kernel = functools.partial(
        _decode_kernel, scale=1.0 / math.sqrt(d), block_k=block_k,
        window=window, kv_steps=kv_steps,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, kv_steps),
        in_specs=[
            pl.BlockSpec((1, 1, 1, d), lambda b_, h_, j_, idx: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, j_, idx, g=group: (b_, h_ // g, j_, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, j_, idx, g=group: (b_, h_ // g, j_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, d), lambda b_, h_, j_, idx: (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), q.dtype),
        interpret=interpret,
    )(jnp.asarray(index, jnp.int32).reshape(1), qh, kh, vh)
    return out.reshape(b, 1, h, d)
