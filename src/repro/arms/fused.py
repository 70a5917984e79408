"""The fused cohort round-step: one jit dispatch per round (DESIGN.md §7).

The per-participant round loop costs H separate jit dispatches plus H host
syncs (``float(loss)`` inside each ``contribution()``) per round.  The fused
hot path stacks the cohort's padded Poisson batches on a leading participant
axis and vmaps the arm's per-silo numerics across it inside ONE jit'd
program — noise keys are pure ``fold_in`` functions of ``(round, index)``,
so batching them changes nothing about what each participant draws.  Metrics
come back as one stacked array: a single host sync per round.

Contract (enforced by ``tests/test_fused.py``):

  * an arm's ``fused_round`` must consume the backend's host rng in exactly
    the order the ``contribution()`` loop would (round, ascending
    participant index), so the two paths see the same Poisson draws;
  * the fused payloads must match the per-participant loop's payloads up to
    vmap-vs-loop float association (ulp-level; the loop path is *not*
    bit-identical to the fused path, which is why the legacy seed-for-seed
    shims in ``repro.core.federation`` pin ``fused_rounds=False``);
  * both backends run the *same* fused program, so cross-backend
    equivalence stays bit-exact with fusion enabled by default.

The in-jit cohort reduction (``seq_tree_sum`` / ``seq_weighted_sum``)
accumulates in ascending-slot order — the same order as the eager
``tree_sum`` over per-participant slices — so an idealized backend that
consumes the fused total and a sim backend that sums delivered slices
agree bit-for-bit.

Every jit entry point on the round hot path is created through
``instrumented_jit`` so ``benchmarks/hotpath.py`` can count program
launches: the fused path dispatches O(1) programs per round, the legacy
loop O(H).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import numpy as np

import repro.obs as obs
from repro.arms.base import Contribution, Participant, poisson_batch

# -- jit dispatch accounting -------------------------------------------------
# Hoisted to ``repro.instrument`` so the serving tier (DESIGN.md §9) shares
# the same counter without importing the arms package; re-exported here for
# every arm module, benchmark and test that grew up on ``fused.X``.
from repro.instrument import (  # noqa: F401
    active_executor,
    execution_context,
    instrumented_jit,
    instrumented_jit_pair,
    jit_dispatches,
    reset_jit_dispatches,
)

PyTree = Any


# -- host-side cohort stacking ----------------------------------------------


@dataclasses.dataclass
class CohortBatch:
    """The active cohort's Poisson draws, stacked to one static shape.

    ``x``/``y`` have leading axis ``n_active`` (plus a steps axis when
    ``steps`` was requested); ``masks`` flags the real examples inside each
    pad; ``counts`` is the per-draw real-example count (int32, same leading
    axes); ``sizes`` is the per-participant total — host ints, known before
    the dispatch, which is what lets aggregate-batch math stay off-device.
    """

    x: np.ndarray
    y: np.ndarray
    masks: np.ndarray
    counts: np.ndarray
    sizes: list[int]


def _repad(arr: np.ndarray, pad_to: int) -> np.ndarray:
    if arr.shape[0] == pad_to:
        return arr
    out = np.zeros((pad_to,) + arr.shape[1:], arr.dtype)
    out[: arr.shape[0]] = arr
    return out


def stack_poisson(
    rng: np.random.Generator,
    participants: Sequence[Participant],
    active: Sequence[int],
    rate: float | Sequence[float],
    pad: int | Sequence[int],
    steps: int | None = None,
) -> CohortBatch:
    """Stack each active participant's Poisson draw(s) to one static shape.

    Consumes ``rng`` in exactly the order the per-participant loop would:
    ascending participant index, and (when ``steps`` is given) each
    participant's local steps drawn consecutively.  If any single draw
    outgrew the configured pad (``poisson_batch`` grows rather than
    truncates), the whole cohort is re-padded to the round's max — masks
    keep the extra rows inert.

    ``rate``/``pad`` may be sequences indexed by *absolute* participant
    index (ragged local-DP arms like primia: every client has its own
    sampling rate and pad); each draw then uses its own rate/pad exactly
    like the per-participant loop, and the stack re-pads every row to the
    cohort max.  Extra zero rows contribute exactly nothing to masked
    sums, so padding never changes any number.

    Under an active mesh execution context the cohort pad is rounded up to
    the mesh's data-axis size (again mask-inert) and the stacked batch
    arrays are marked for sharding along the example axis.
    """
    t0 = obs.now()  # host-RNG phase: the one per-round host-side cost
    rate_of = (rate.__getitem__ if not isinstance(rate, (int, float))
               else lambda i: rate)
    pad_of = (pad.__getitem__ if not isinstance(pad, int)
              else lambda i: pad)
    executor = active_executor()
    k_steps = 1 if steps is None else steps
    draws: list[list[tuple[dict, np.ndarray, int]]] = []
    pad_to = max(pad_of(i) for i in active)
    for i in active:
        row = []
        for _ in range(k_steps):
            b, m, k = poisson_batch(rng, participants[i], rate_of(i),
                                    pad_of(i))
            pad_to = max(pad_to, len(m))
            row.append((b, m, k))
        draws.append(row)
    if executor is not None:
        pad_to = executor.round_pad(pad_to)

    def gather(fn):
        return np.stack([
            np.stack([fn(d) for d in row]) for row in draws
        ])

    x = gather(lambda d: _repad(d[0]["x"], pad_to))
    y = gather(lambda d: _repad(d[0]["y"], pad_to))
    masks = gather(lambda d: _repad(d[1], pad_to))
    counts = np.asarray(
        [[d[2] for d in row] for row in draws], np.int32
    )
    sizes = [int(c) for c in counts.sum(axis=1)]
    if steps is None:  # collapse the singleton steps axis
        x, y, masks, counts = x[:, 0], y[:, 0], masks[:, 0], counts[:, 0]
    if executor is not None:
        example_axis = 1 if steps is None else 2
        for arr in (x, y, masks):
            executor.mark(arr, axis=example_axis)
    obs.complete("host_rng.stack_poisson", t0, cat="rng",
                 cohort=len(active), pad=pad_to)
    return CohortBatch(x=x, y=y, masks=masks, counts=counts, sizes=sizes)


# -- in-jit cohort reductions ------------------------------------------------


def seq_tree_sum(stack: PyTree, n: int) -> PyTree:
    """Sum over the leading axis in ascending-slot order (NOT a reduce —
    association must match the eager ``tree_sum`` over slices bit-for-bit)."""
    total = jax.tree_util.tree_map(lambda x: x[0], stack)
    for s in range(1, n):
        total = jax.tree_util.tree_map(
            lambda a, x, s=s: a + x[s], total, stack
        )
    return total


def seq_weighted_sum(stack: PyTree, weights, n: int) -> PyTree:
    """``sum_s w[s] * stack[s]`` in ascending-slot order (same association
    as the eager size-weighted FedAvg average)."""
    total = jax.tree_util.tree_map(lambda x: weights[0] * x[0], stack)
    for s in range(1, n):
        total = jax.tree_util.tree_map(
            lambda a, x, s=s: a + weights[s] * x[s], total, stack
        )
    return total


# -- fused output -> per-participant contributions --------------------------


def build_contributions(
    active: Sequence[int],
    payload_stack: PyTree,
    losses,
    sizes: Sequence[int],
    need_payloads: bool,
) -> dict[int, Contribution]:
    """One host sync for the whole cohort's metrics (and, when the backend
    needs per-participant payloads — SecAgg uploads or sim transport — one
    transfer for the whole payload stack; the slices are numpy views).

    With ``need_payloads=False`` the payloads stay on device and the
    per-participant ``payload`` is ``None`` — the idealized backend serves
    the aggregate from the reduced tree instead, or, with SecAgg, runs the
    field arithmetic over the payload stack where it lies
    (``cohort_output``).

    The ``fused_round.fetch`` span covers the wait for the cohort program
    and that one transfer; its ``bytes`` is what came to the host.
    """
    t0 = obs.now()
    loss_vals = None
    if need_payloads:
        if losses is not None:
            payload_stack, loss_vals = jax.device_get((payload_stack, losses))
        else:
            payload_stack = jax.device_get(payload_stack)
    elif losses is not None:
        loss_vals = np.asarray(losses)
    if t0 is not None:
        fetched = (payload_stack if need_payloads else None, loss_vals)
        obs.complete("fused_round.fetch", t0, cat="train",
                     bytes=sum(np.asarray(leaf).nbytes for leaf
                               in jax.tree_util.tree_leaves(fetched)))
    if need_payloads:
        slices = [
            jax.tree_util.tree_map(lambda a, s=s: a[s], payload_stack)
            for s in range(len(active))
        ]
    else:
        slices = [None] * len(active)
    return {
        i: Contribution(
            payload=slices[s],
            size=int(sizes[s]),
            loss=None if loss_vals is None else float(loss_vals[s]),
        )
        for s, i in enumerate(active)
    }


def cohort_output(stack: PyTree, reduced: PyTree | None,
                  need_payloads: bool) -> PyTree | None:
    """``fused_round``'s second element: the in-jit reduced aggregate when
    the program made one; with the payloads withheld and no reduction
    asked for, the (n, ...) payload stack itself, which the backend then
    aggregates on the device (SecAgg on the idealized backend)."""
    if reduced is None and not need_payloads:
        return stack
    return reduced
