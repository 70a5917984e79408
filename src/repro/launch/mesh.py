"""The one mesh factory of the repo (functions, not constants — nothing here
touches jax device state at import time).

Every mesh is built by ``_mesh`` with ``AxisType.Auto`` axes: GSPMD then
propagates shardings from the placed operands, which is what the ``shard``
backend, the dry-run and the SPMD tests rely on.  (``jax.make_mesh`` defaults
to Explicit axes, under which those programs fail to trace.)
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e production mesh: 16x16 per pod; 2 pods multi-pod.

    Axes: ("data", "model") single-pod; ("pod", "data", "model") multi-pod.
    DeCaPH maps hospitals onto ("pod", "data") — the secure-aggregation sum is
    the gradient reduction over those axes (DESIGN.md §3).  The `shard`
    backend accepts these meshes directly
    (``ShardedRunner(mesh=make_production_mesh(multi_pod=True))``): hospitals
    shard over ("pod", "data"), model-parallel params over ("model",).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2, *, multi_pod: bool = False):
    """Small mesh: CPU tests (XLA host device count >= product) or one
    four-chip host."""
    if multi_pod:
        return _mesh((2, n_data, n_model), ("pod", "data", "model"))
    return _mesh((n_data, n_model), ("data", "model"))


def make_host_data_mesh(n_data: int | None = None):
    """1-D ("data",) mesh over ``n_data`` devices (default: all available).

    The federated SPMD backend (``launch.federated.ShardedRunner``) shards
    the fused cohort round-step's *example* axis over it; the tabular-scale
    params stay replicated.  On CPU CI the devices come from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
    """
    n = n_data or jax.device_count()
    return _mesh((n,), ("data",))


def make_host_mesh():
    """("data", "model") mesh over every local device: model extent 2 when
    the device count is even, else 1 (``launch/train.py``)."""
    n = jax.device_count()
    model = 2 if n > 1 and n % 2 == 0 else 1
    return _mesh((n // model, model), ("data", "model"))


def data_axes(mesh) -> tuple[str, ...]:
    """Axes carrying the batch/participant dimension."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a == "model")
