"""Jit'd public wrapper for decode attention with CPU fallback."""

from __future__ import annotations

import jax

from repro.kernels.decode_attention.kernel import (
    DEFAULT_BLOCK_K,
    decode_attention_pallas,
)
from repro.kernels.decode_attention.ref import decode_attention_ref


def decode_attention(q, k, v, index, *, window: int | None = None,
                     block_k: int = DEFAULT_BLOCK_K,
                     force_kernel: bool = False):
    """Single-query decode attention. TPU -> Pallas; CPU -> oracle."""
    backend = jax.default_backend()
    if backend == "tpu":
        return decode_attention_pallas(q, k, v, index, window=window,
                                       block_k=block_k)
    if force_kernel:
        return decode_attention_pallas(q, k, v, index, window=window,
                                       block_k=block_k, interpret=True)
    return decode_attention_ref(q, k, v, index, window=window)
