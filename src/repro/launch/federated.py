"""The ``shard`` backend: SPMD execution of the fused cohort round-step.

This is the registry module that carries the fused hot path (DESIGN.md §7)
into the SPMD world of ``launch/``: ``ShardedRunner`` defaults to a 1-D
``data`` mesh via ``launch.mesh.make_host_data_mesh`` and also accepts the
full production ``("pod", "data", "model")`` meshes from
``launch.mesh.make_production_mesh`` / ``make_debug_mesh``; either way it
executes every fused cohort program — the exact same traced function the
idealized backend jits — under GSPMD with explicit placements:

  * on a 1-D mesh the stacked cohort batches (``fused.stack_poisson``
    output) are sharded along the *example* axis over the mesh's data axes —
    the cohort pad is rounded up to the data-axis size first, which is free
    because masks keep pad rows exactly inert;
  * on a pod mesh the *hospital* (participant) axis shards over the combined
    ``("pod", "data")`` axes instead, whenever the cohort size divides them —
    each pod owns a slice of the federation and the in-jit cohort reduction
    (DeCaPH's SecAgg-summed aggregate) lowers to cross-pod all-reduces, never
    a host gather.  The participant axis is NEVER padded: a padded slot would
    add a phantom per-participant noise share.  Non-divisible cohorts fall
    back to the example-axis rule above;
  * on meshes with a ``model`` axis, model-parallel params shard over
    ``("model",)`` per the ``launch/sharding.py`` logical-axis rules
    (mlp/qheads/kv_heads/vocab → "model"; tabular params have no encoded
    axes and stay replicated);
  * every other operand (noise salts, cohort index vectors, control-variate
    stacks) is replicated, matching the ``launch/sharding.py`` fallback rule
    for non-divisible leaves;
  * outputs get explicit replicated out-shardings: the per-participant
    payload stacks and the in-jit reduced aggregate come back whole, so the
    arm's eager aggregation math is identical to the idealized backend's.

The gradient reductions over the sharded example axis lower to all-reduces
over ``data`` — exactly the collective DeCaPH's secure sum maps onto in the
production mesh story (DESIGN.md §3).  Partitioned reductions re-associate
float math, so ``shard`` sits in its own ``bit_exact_group`` ("spmd"):
against the host backends it agrees to the fused-vs-loop tolerance class
(atol 1e-5 on the tabular presets; see ``tests/test_backends.py``), not bit
for bit.

Capability record: fused-only (there is no per-participant loop to fall
back to) and no SecAgg (the point of the fast path is that payloads never
leave the device; a spec asking for ciphertext uploads here fails at
validation time instead of silently shipping plaintext).
"""

from __future__ import annotations

from typing import Any

import numpy as np

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.arms import fused
from repro.arms.backends import (
    BackendInfo,
    RunSetup,
    compatibility_error,
    register_backend,
)
from repro.arms.runners import LocalRunner
from repro.launch.mesh import data_axes, make_host_data_mesh
from repro.launch.sharding import ShardingPolicy, param_specs

_DEVICE_HINT = (
    "needs >= 2 XLA devices; on CPU launch with "
    "XLA_FLAGS=--xla_force_host_platform_device_count=8"
)


class MeshExecutor:
    """Re-dispatches ``instrumented_jit`` cohort programs onto a mesh.

    Installed around each fused round via ``fused.execution_context``; the
    wrapper hands over ``(raw fn, jit kwargs, args)`` and this executor
    places the operands (example axis sharded for arrays marked by
    ``stack_poisson``, everything else replicated), stages the program once
    per wrapper with explicit replicated out-shardings, and launches it.
    Python-int operands (static argnums) pass through untouched.
    """

    def __init__(self, mesh) -> None:
        self.mesh = mesh
        axes = data_axes(mesh)
        self._axis_entry = axes if len(axes) > 1 else axes[0]
        self._pod_mesh = len(axes) > 1  # ("pod","data",...) production shape
        self.data_size = int(np.prod([mesh.shape[a] for a in axes]))
        self._replicated = NamedSharding(mesh, P())
        # model-parallel param placement (pod meshes): TP only — FSDP would
        # split the embed dim over the same axes that carry hospitals
        self._param_policy = (
            ShardingPolicy(fsdp=False, tp=True)
            if "model" in mesh.axis_names else None
        )
        # id -> (array, placement, is a cohort batch rather than a param)
        self._marks: dict[int, tuple[Any, NamedSharding, bool]] = {}
        self._staged: dict[Any, Any] = {}
        self.sharded_puts = 0  # placements that actually split an axis
        self.participant_shards = 0  # cohorts split over ("pod","data")
        self.param_shards = 0  # param leaves placed over ("model",)
        # device -> index of its shard, for the last cohort batch placed
        self.cohort_layout: dict = {}

    # -- hooks consumed by repro.arms.fused -----------------------------------

    def round_pad(self, pad: int) -> int:
        """Round a cohort pad up to a multiple of the data-axis size."""
        return -(-pad // self.data_size) * self.data_size

    def mark(self, arr: np.ndarray, axis: int) -> None:
        """Declare ``arr`` a cohort batch to shard along ``axis``.

        Pod meshes prefer splitting the *participant* axis (0) over the
        combined ``("pod","data")`` axes — but only when the cohort size
        divides them exactly: unlike the example axis (mask-inert pad rows),
        a padded participant slot would draw its own DP noise share, so the
        fallback is the example-axis split, never padding.
        """
        if self._pod_mesh and arr.shape[0] % self.data_size == 0:
            spec = P(*[self._axis_entry if d == 0 else None
                       for d in range(arr.ndim)])
            self._marks[id(arr)] = (arr, NamedSharding(self.mesh, spec), True)
            self.participant_shards += 1
            return
        if arr.shape[axis] % self.data_size:
            return  # replication fallback (same rule as launch/sharding.py)
        spec = P(*[self._axis_entry if d == axis else None
                   for d in range(arr.ndim)])
        self._marks[id(arr)] = (arr, NamedSharding(self.mesh, spec), True)

    def mark_params(self, params) -> None:
        """Declare model params for TP placement over the ``model`` axis.

        No-op on meshes without a ``model`` axis.  Leaves whose keys encode
        no shardable logical axes (all tabular models) resolve to replicated
        specs and are skipped — placement falls through to the default.
        """
        if self._param_policy is None:
            return
        specs = param_specs(params, self.mesh, self._param_policy)
        for leaf, sh in zip(jax.tree_util.tree_leaves(params),
                            jax.tree_util.tree_leaves(specs)):
            if sh.spec != P(*([None] * leaf.ndim)):
                self._marks[id(leaf)] = (leaf, sh, False)
                self.param_shards += 1

    def begin_round(self) -> None:
        self._marks.clear()

    def execute(self, wrapper, args, kwargs):
        staged = self._staged.get(wrapper)
        if staged is None:
            # donation is dropped: donated buffers cannot be re-placed with
            # device_put round after round, and the state stacks involved
            # (scaffold's control variates) are tabular-scale
            jkw = {k: v for k, v in wrapper.jit_kwargs.items()
                   if k != "donate_argnums"}
            staged = jax.jit(wrapper.fn, out_shardings=self._replicated,
                             **jkw)
            self._staged[wrapper] = staged
        placed = jax.tree_util.tree_map(self._place_leaf, (args, kwargs))
        # the mesh in context lets kernels the compiler cannot partition
        # (Pallas on TPU) run per device under a shard_map
        with jax.set_mesh(self.mesh):
            return staged(*placed[0], **placed[1])

    def _place_leaf(self, leaf):
        if isinstance(leaf, (bool, int, float)):
            return leaf  # static argnums stay python scalars
        mark = self._marks.get(id(leaf))
        if mark is not None:
            self.sharded_puts += 1
            placed = jax.device_put(leaf, mark[1])
            if mark[2]:
                self.cohort_layout = {s.device: s.index
                                      for s in placed.addressable_shards}
            return placed
        return jax.device_put(leaf, self._replicated)


@register_backend(BackendInfo(
    name="shard",
    supports_fused=True,
    supports_secagg=False,
    supports_sim_time=False,
    fused_only=True,
    bit_exact_group="spmd",
    device_requirements=_DEVICE_HINT,
    description="SPMD execution of the fused cohort round-step on a device "
                "mesh (example axis sharded over data, params replicated)",
))
class ShardedRunner(LocalRunner):
    """Idealized round schedule, SPMD round numerics.

    Inherits the lockstep cohort/round loop from ``LocalRunner`` (everyone
    online, communication free) and overrides the fused-program seam so the
    cohort step runs sharded on the mesh.
    """

    def __init__(self, topo=None, *, mesh=None) -> None:
        super().__init__(topo=topo)
        if mesh is None:
            reason = self.available()
            if reason is not None:
                raise RuntimeError(f"backend 'shard' unavailable: {reason}")
            mesh = make_host_data_mesh()
        self.mesh = mesh
        self.executor = MeshExecutor(mesh)

    @classmethod
    def from_setup(cls, setup: RunSetup) -> "ShardedRunner":
        return cls(topo=setup.topo, mesh=setup.mesh)

    @classmethod
    def available(cls) -> str | None:
        if jax.device_count() < 2:
            return _DEVICE_HINT
        return None

    def run(self, arm):
        # belt and braces under direct construction: repro.arms.run already
        # negotiates these pairs — same rules, single source of truth
        err = compatibility_error(
            type(arm), self.info, use_secagg=arm.cfg.use_secagg,
            fused_rounds=arm.cfg.fused_rounds,
        )
        if err is not None:
            raise ValueError(err)
        return super().run(arm)

    def _fused_round(self, arm, params, active, t, rng, *,
                     need_payloads, need_reduced):
        self.executor.begin_round()
        self.executor.mark_params(params)
        with fused.execution_context(self.executor):
            fr = super()._fused_round(arm, params, active, t, rng,
                                      need_payloads=need_payloads,
                                      need_reduced=need_reduced)
        if fr is None:
            raise RuntimeError(
                f"arm {arm.name!r} fell back to the per-participant loop "
                "under the fused-only 'shard' backend"
            )
        return fr
