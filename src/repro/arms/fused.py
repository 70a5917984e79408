"""The fused cohort round-step: one jit dispatch per round (DESIGN.md §7).

The per-participant round loop costs H separate jit dispatches plus H host
syncs (``float(loss)`` inside each ``contribution()``) per round.  The fused
hot path lays the cohort's padded Poisson batches on a leading participant
axis (stacked on the host, or gathered inside the program from the rows
``device_rows`` keeps on the device) and vmaps the arm's per-silo numerics
across it inside ONE jit'd program — noise keys are pure ``fold_in`` functions of ``(round, index)``,
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
from repro.arms.base import Contribution, Participant, poisson_rows

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


# -- the cohort's Poisson batch ---------------------------------------------


@dataclasses.dataclass
class CohortBatch:
    """The active cohort's Poisson draws, at one static shape.

    ``rows`` holds, per slot, the example's global row index: the
    participants' examples concatenated in participant order, as
    ``device_rows`` lays them out, with pad slots at the zero row one past
    the last example.  ``masks`` flags the real examples inside each pad;
    ``counts`` is the per-draw real-example count (int32); all three share
    the leading axes ``n_active`` (plus a steps axis when ``steps`` was
    requested).  ``sizes`` is the per-participant total — host ints, known
    before the dispatch, which is what lets aggregate-batch math stay
    off-device.

    ``x``/``y`` are the examples the rows pick, stacked on the host (zero
    rows in the pad slots): built by ``stack_poisson`` when asked to stack,
    else on first read and kept in ``stacked``.
    """

    masks: np.ndarray
    counts: np.ndarray
    sizes: list[int]
    rows: np.ndarray
    source: Sequence[Participant] = ()
    stacked: dict[str, np.ndarray] = dataclasses.field(
        default_factory=dict, repr=False)

    @property
    def x(self) -> np.ndarray:
        return self._stack("x")

    @property
    def y(self) -> np.ndarray:
        return self._stack("y")

    def _stack(self, leaf: str) -> np.ndarray:
        if leaf not in self.stacked:
            self.stacked[leaf] = _host_rows(self.source, self.rows, leaf)
        return self.stacked[leaf]


def _row_starts(participants: Sequence[Participant]) -> np.ndarray:
    """Each participant's first global row, then the zero row's index."""
    return np.cumsum([0] + [len(p) for p in participants])


def _host_rows(participants: Sequence[Participant], rows: np.ndarray,
               leaf: str) -> np.ndarray:
    """``leaf`` of the examples ``rows`` picks, gathered on the host."""
    arrs = [getattr(p, leaf) for p in participants]
    starts = _row_starts(participants)
    dtype = np.result_type(*{a.dtype for a in arrs})
    out = np.zeros(rows.shape + arrs[0].shape[1:], dtype)
    flat_out = out.reshape((-1,) + arrs[0].shape[1:])
    flat = rows.reshape(-1)
    real = flat < starts[-1]
    silo = np.searchsorted(starts, flat, side="right") - 1
    for s in np.unique(silo[real]):
        sel = np.flatnonzero(real & (silo == s))
        flat_out[sel] = arrs[s][flat[sel] - starts[s]]
    return out


def device_rows(participants: Sequence[Participant]) -> tuple[jax.Array,
                                                              jax.Array]:
    """Every participant's ``x`` and ``y`` on the device, concatenated in
    participant order with one zero row appended: the arrays
    ``CohortBatch.rows`` indexes, so a gather there gives the host stack."""
    out = []
    for leaf in ("x", "y"):
        arrs = [getattr(p, leaf) for p in participants]
        n = sum(len(a) for a in arrs)
        dtype = np.result_type(*{a.dtype for a in arrs})
        host = np.zeros((n + 1,) + arrs[0].shape[1:], dtype)
        np.concatenate(arrs, out=host[:n])
        out.append(jax.device_put(host))
    return out[0], out[1]


def stack_poisson(
    rng: np.random.Generator,
    participants: Sequence[Participant],
    active: Sequence[int],
    rate: float | Sequence[float],
    pad: int | Sequence[int],
    steps: int | None = None,
    *,
    host_stack: bool = True,
) -> CohortBatch:
    """Draw each active participant's Poisson batch(es) at one static shape.

    Consumes ``rng`` in exactly the order the per-participant loop would:
    ascending participant index, and (when ``steps`` is given) each
    participant's local steps drawn consecutively.  If any single draw
    outgrew the configured pad (``poisson_batch`` grows rather than
    truncates), the whole cohort is re-padded to the round's max — masks
    keep the extra rows inert.

    ``rate``/``pad`` may be sequences indexed by *absolute* participant
    index (ragged local-DP arms like primia: every client has its own
    sampling rate and pad); each draw then uses its own rate/pad exactly
    like the per-participant loop, and the batch re-pads every row to the
    cohort max.  Extra zero rows contribute exactly nothing to masked
    sums, so padding never changes any number.

    With ``host_stack`` the examples are stacked on the host here
    (``CohortBatch.x``/``y``); without it only the row indices are made,
    for a program that gathers from ``device_rows``.  Under an active mesh
    execution context the batch is always stacked: the cohort pad is
    rounded up to the mesh's data-axis size (again mask-inert) and the
    stacked arrays are marked for sharding along the example axis.
    """
    t0 = obs.now()  # host-RNG phase: the one per-round host-side cost
    rate_of = (rate.__getitem__ if not isinstance(rate, (int, float))
               else lambda i: rate)
    pad_of = (pad.__getitem__ if not isinstance(pad, int)
              else lambda i: pad)
    executor = active_executor()
    k_steps = 1 if steps is None else steps
    draws: list[list[np.ndarray]] = []
    pad_to = max(pad_of(i) for i in active)
    for i in active:
        row = []
        for _ in range(k_steps):
            idx, grown = poisson_rows(rng, len(participants[i]), rate_of(i),
                                      pad_of(i))
            pad_to = max(pad_to, grown)
            row.append(idx)
        draws.append(row)
    if executor is not None:
        pad_to = executor.round_pad(pad_to)

    starts = _row_starts(participants)
    shape = (len(active), k_steps, pad_to)
    rows = np.full(shape, starts[-1], np.int32)
    masks = np.zeros(shape, np.float32)
    for s, (i, row) in enumerate(zip(active, draws)):
        for k, idx in enumerate(row):
            rows[s, k, :len(idx)] = starts[i] + idx
            masks[s, k, :len(idx)] = 1.0
    counts = np.asarray([[len(idx) for idx in row] for row in draws],
                        np.int32)
    sizes = [int(c) for c in counts.sum(axis=1)]
    if steps is None:  # collapse the singleton steps axis
        rows, masks, counts = rows[:, 0], masks[:, 0], counts[:, 0]
    cb = CohortBatch(masks=masks, counts=counts, sizes=sizes, rows=rows,
                     source=participants)
    if host_stack or executor is not None:
        x, y = cb.x, cb.y
        if executor is not None:
            example_axis = 1 if steps is None else 2
            for arr in (x, y, masks):
                executor.mark(arr, axis=example_axis)
    obs.complete("host_rng.stack_poisson", t0, cat="rng",
                 cohort=len(active), pad=pad_to,
                 host_bytes=sum(a.nbytes for a in cb.stacked.values()))
    return cb


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
