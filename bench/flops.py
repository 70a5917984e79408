"""Operation counts from a configuration's widths, and the chip's peaks.

``peak(kind)`` reads ``peaks.json``, keyed by JAX's ``device_kind``; a kind
that is not in the table is an error, never a default.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).with_name("peaks.json")


def mlp_train_flops_per_example(sizes) -> int:
    """Forward and backward FLOPs of one example through a dense MLP:
    2 per multiply-add forward, 4 backward (input and weight gradients),
    over every ``fan_in x fan_out`` weight matrix; biases and activations
    are not counted."""
    return 6 * sum(a * b for a, b in zip(sizes[:-1], sizes[1:]))


def peak(kind: str) -> dict:
    """The peaks of one chip of ``kind``; raises ``KeyError`` for a kind the
    table does not hold."""
    table = json.loads(PEAKS.read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS.name}; "
                       f"known: {sorted(table)}")
    return table[kind]
