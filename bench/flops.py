"""The chip's peaks.  A model's operation count is its kind's
(``bench/kinds/<kind>.py``, ``train_flops_per_example``).

``peak(kind)`` reads ``peaks.json``, keyed by JAX's ``device_kind``; a kind
that is not in the table is an error, never a default.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).with_name("peaks.json")


def peak(kind: str) -> dict:
    """The peaks of one chip of ``kind``; raises ``KeyError`` for a kind the
    table does not hold."""
    table = json.loads(PEAKS.read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS.name}; "
                       f"known: {sorted(table)}")
    return table[kind]
