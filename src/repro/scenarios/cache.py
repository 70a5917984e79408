"""Content-addressed on-disk cache for sweep cell results.

One JSON file per (cell, device), named by ``ResultCache.key``: the
``ScenarioSpec.spec_hash()`` together with the platform and device kind this
process computes on.  Re-running a sweep therefore only executes new/changed
cells — a sweep interrupted at cell 40/112 resumes where it left off, and
editing one axis value only invalidates the cells it touches — while a row
computed on one device is never served as another's (a CPU row copied along
with a checkout is a miss on the chip).  Every row names its ``device``.

Robustness contract (tested in ``tests/test_scenarios.py``): a corrupted or
stale entry (unparseable JSON, schema mismatch, key/spec mismatch, missing
result fields) is treated as a miss — logged loudly, evicted, recomputed —
never an exception and never silently wrong data.
"""

from __future__ import annotations

import functools
import json
import logging
import os
from pathlib import Path

from repro.canon import content_hash
from repro.scenarios.spec import ScenarioSpec

logger = logging.getLogger(__name__)

CACHE_SCHEMA = 1
DEFAULT_CACHE_DIR = ".sweep_cache"

# every field the report layer dereferences must be present, or the entry
# is treated as corrupted — served entries must never crash reporting
_REQUIRED_RESULT_KEYS = frozenset(
    {"name", "arm", "backend", "hospitals", "model_size", "model_params",
     "rounds_completed", "epsilon", "accuracy", "wall_clock",
     "bytes_on_wire", "recoveries", "device"}
)


def device_identity() -> dict:
    """The device this process computes on: what every result row names."""
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count()}


class ResultCache:
    """Spec-hash-addressed store of cell results."""

    def __init__(self, root: str | os.PathLike = DEFAULT_CACHE_DIR) -> None:
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)

    @functools.cached_property
    def device(self) -> dict:
        return device_identity()

    def key(self, spec: ScenarioSpec) -> str:
        """The spec's hash bound to this process's platform and device kind."""
        return content_hash({"spec": spec.spec_hash(),
                             "platform": self.device["platform"],
                             "kind": self.device["kind"]}, chars=20)

    def path(self, spec: ScenarioSpec) -> Path:
        return self.root / f"{self.key(spec)}.json"

    def get(self, spec: ScenarioSpec) -> dict | None:
        """The cached result for ``spec``, or None (miss / evicted)."""
        path = self.path(spec)
        if not path.exists():
            return None
        try:
            entry = json.loads(path.read_text())
            if entry["schema"] != CACHE_SCHEMA:
                raise ValueError(f"schema {entry['schema']} != {CACHE_SCHEMA}")
            if entry["key"] != self.key(spec):
                raise ValueError("key does not match spec hash and device")
            result = entry["result"]
            missing = _REQUIRED_RESULT_KEYS - set(result)
            if missing:
                raise ValueError(f"result missing fields {sorted(missing)}")
        except (OSError, ValueError, KeyError, TypeError) as e:
            logger.warning(
                "corrupted cache entry %s for %s (%s); evicting and "
                "recomputing", path, spec.name or spec.spec_hash(), e,
            )
            try:
                path.unlink()
            except OSError:
                pass
            return None
        return result

    def put(self, spec: ScenarioSpec, result: dict) -> Path:
        """Atomically persist ``result`` under ``spec``'s hash."""
        path = self.path(spec)
        entry = {
            "schema": CACHE_SCHEMA,
            "key": self.key(spec),
            "spec": spec.to_dict(),
            "result": result,
        }
        tmp = path.with_suffix(".tmp")
        tmp.write_text(json.dumps(entry, indent=2, sort_keys=True))
        os.replace(tmp, path)
        return path

    def __len__(self) -> int:
        return sum(1 for _ in self.root.glob("*.json"))
