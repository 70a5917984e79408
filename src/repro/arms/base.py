"""The Arm/Backend contract: write an arm's numerics once, run it anywhere.

An ``Arm`` declares *what* a federation protocol computes each round — local
updates, aggregation rule, privacy accounting, and what goes on the wire —
and nothing about *when*.  Two backends execute the same arm object:

  * ``LocalRunner`` — idealized lockstep: every hospital infinitely fast and
    always online, communication free (the paper's utility experiments);
  * ``SimRunner``  — the discrete-event engine from ``repro.sim``: simulated
    wall-clock, bytes-on-wire, stragglers, dropouts, SecAgg mask recovery.

The contract (DESIGN.md §5): an arm may never observe simulated time, node
availability, or the engine.  Its numerics must be a deterministic function
of (config seed, round index, participant index) plus the backend-supplied
draw stream, so that the two backends produce the same training trajectory
whenever the simulated conditions are ideal.

Randomness rules that make cross-backend equivalence hold:

  * round arms share one host ``np.random.Generator`` consumed strictly in
    (round, ascending participant index) order — both backends iterate the
    round's active cohort the same way;
  * node arms must hold one independent stream per node (the event backend
    interleaves nodes in simulated-time order, so a shared stream would be
    consumed in a schedule-dependent order);
  * JAX noise keys are derived by pure ``fold_in`` of (salt + round, index)
    and therefore never depend on execution order.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dp as dp_lib

PyTree = Any

logger = logging.getLogger(__name__)


# -- model / data ------------------------------------------------------------


@dataclasses.dataclass
class Model:
    """Functional model triple shared by every arm."""

    init_fn: Callable[[jax.Array], PyTree]
    loss_fn: Callable[[PyTree, PyTree], jax.Array]  # (params, one example) -> scalar
    predict_fn: Callable[[PyTree, jax.Array], jax.Array]
    # optional capability: ghost-clipping support (arms/clipping.py).  Set by
    # constructors that know the model is a dense decoder stack with untied
    # embeddings; None = faithful per-example clipping only.
    ghost: Any | None = None


@dataclasses.dataclass
class Participant:
    """One hospital: a private (X, y) shard."""

    x: np.ndarray
    y: np.ndarray

    def __len__(self) -> int:
        return len(self.x)


def _global_stats(parts: Sequence[Participant]) -> tuple[np.ndarray, np.ndarray]:
    """Preparation-phase global mean/std via (conceptually) SecAgg sums."""
    n = sum(len(p) for p in parts)
    s = sum(p.x.sum(axis=0) for p in parts)
    mean = s / n
    sq = sum(((p.x - mean) ** 2).sum(axis=0) for p in parts)
    std = np.sqrt(sq / n) + 1e-8
    return mean.astype(np.float32), std.astype(np.float32)


def normalize_participants(parts: Sequence[Participant]) -> list[Participant]:
    mean, std = _global_stats(parts)
    return [Participant((p.x - mean) / std, p.y) for p in parts]


# -- configuration -----------------------------------------------------------


@dataclasses.dataclass
class ArmConfig:
    """One config for every arm on every backend.

    Supersedes ``FederationConfig`` and ``SimConfig`` (both remain as
    aliases); arm-specific knobs are simply ignored by arms that do not use
    them, which keeps scenario sweeps (same config, many arms) trivial.
    """

    rounds: int = 100
    batch_size: int = 64           # desired aggregate mini-batch size B
    lr: float = 0.1
    weight_decay: float = 0.0
    dp: dp_lib.DPConfig = dataclasses.field(default_factory=dp_lib.DPConfig)
    epsilon_budget: float | None = None   # stop when the accountant exceeds it
    use_secagg: bool = True        # run the real fixed-point SecAgg protocol
    secagg_frac_bits: int = 16
    secagg_threshold: int | None = None  # None -> majority of round's cohort
    fl_local_steps: int = 1        # >1 = FedAvg (weight averaging) for "fl"
    fedprox_mu: float = 0.1        # proximal-term weight for "fedprox"
    leader_strategy: str = "uniform"
    fused_rounds: bool = True      # cohort-batched round step (DESIGN.md §7)
    participation_rate: float = 1.0  # Poisson cohort subsampling q (population
                                     # backend; 1.0 = everyone, every round)
    clipping: str = "auto"         # per-example clipping path: "auto" takes
                                   # ghost when Model.ghost is set, "ghost"
                                   # demands it (validation error otherwise),
                                   # "per-example" forces the faithful path
    seed: int = 0
    eval_every: int = 0            # 0 = never
    max_pad_batch: int | None = None  # static padded per-silo batch (jit shapes)
    # systems knobs (sim backend only)
    bytes_per_param: float = 4.0
    fl_server: int = 0             # star hub for fl/primia
    # gossip-family knobs
    gossip_steps: int | None = None  # local steps per node; None -> rounds
    gossip_every: int = 1            # exchange after every k-th local step


# -- shared numerics helpers -------------------------------------------------


def poisson_rows(
    rng: np.random.Generator,
    n: int,
    rate: float,
    pad_to: int,
) -> tuple[np.ndarray, int]:
    """``poisson_batch``'s draw alone: the chosen row indices of a silo of
    ``n`` examples and the pad that holds them (``pad_to``, or grown)."""
    idx = np.nonzero(rng.random(n) < rate)[0]
    k = len(idx)
    if k > pad_to:
        grown = 1 << int(np.ceil(np.log2(k)))
        logger.warning(
            "poisson_batch: draw of %d examples exceeded the padded batch %d; "
            "growing the pad to %d for this round (jit retrace). Raise "
            "max_pad_batch to avoid this.", k, pad_to, grown,
        )
        pad_to = grown
    return idx, pad_to


def poisson_batch(
    rng: np.random.Generator,
    part: Participant,
    rate: float,
    pad_to: int,
) -> tuple[dict[str, np.ndarray], np.ndarray, int]:
    """Poisson-sample a silo mini-batch, padded to a static shape + mask.

    The returned arrays have leading dimension ``pad_to`` — unless the
    Poisson draw selected *more* than ``pad_to`` examples, in which case the
    pad grows (next power of two that fits) rather than silently truncating
    the draw.  Truncation would bias the subsampling distribution and void
    the subsampled-RDP privacy analysis, so it must never happen quietly;
    the growth is logged because it retriggers jit tracing for that shape.
    """
    idx, pad_to = poisson_rows(rng, len(part), rate, pad_to)
    k = len(idx)
    xb = np.zeros((pad_to,) + part.x.shape[1:], part.x.dtype)
    yb = np.zeros((pad_to,) + part.y.shape[1:], part.y.dtype)
    xb[:k] = part.x[idx]
    yb[:k] = part.y[idx]
    mask = np.zeros((pad_to,), np.float32)
    mask[:k] = 1.0
    return {"x": xb, "y": yb}, mask, k


def sgd_update(params: PyTree, grads: PyTree, lr: float, wd: float) -> PyTree:
    return jax.tree_util.tree_map(
        lambda p, g: p - lr * (g + wd * p), params, grads
    )


def tree_sum(trees: Sequence[PyTree]) -> PyTree:
    """Elementwise sum of a non-empty sequence of pytrees (stable order)."""
    return jax.tree_util.tree_map(lambda *xs: sum(xs[1:], xs[0]), *trees)


def tree_scale(tree: PyTree, s: float) -> PyTree:
    return jax.tree_util.tree_map(lambda x: x * s, tree)


def tree_div(tree: PyTree, d: float) -> PyTree:
    """Elementwise ``x / d`` (NOT ``x * (1/d)`` — one ulp matters for the
    seed-for-seed guarantee of the legacy shims)."""
    return jax.tree_util.tree_map(lambda x: x / d, tree)


def tree_bytes(tree: PyTree, bytes_per_param: float) -> float:
    """Bytes on the wire for one serialised copy of ``tree``."""
    return bytes_per_param * sum(
        int(np.prod(np.shape(leaf)) or 1)
        for leaf in jax.tree_util.tree_leaves(tree)
    )


def default_pad(rate: float, participants: Sequence[Participant],
                cfg: ArmConfig) -> int:
    """Static padded batch: 4x the largest silo's expected draw (legacy rule)."""
    return cfg.max_pad_batch or max(
        8, int(rate * max(len(p) for p in participants) * 4)
    )


# -- the per-round exchange types --------------------------------------------


@dataclasses.dataclass
class Contribution:
    """What one participant produces in one round.

    ``payload`` is the pytree that goes on the wire (gradient sum, noised
    gradient, or local weights — the arm decides); ``size`` is the number of
    real examples consumed (drives the sim backend's compute time and the
    aggregate batch count); ``loss`` is optional telemetry.
    """

    payload: PyTree
    size: int
    loss: float | None = None


@dataclasses.dataclass
class RoundOutcome:
    """What an arm's ``aggregate`` returns to the backend."""

    params: PyTree
    stepped: bool                 # False -> round void (no model update)
    loss: float = float("nan")
    aggregate_batch: int = 0


class AggregationServices:
    """Backend-provided aggregation primitives (see DESIGN.md §5).

    Secure aggregation is a *backend* service: the idealized backend runs the
    honest-but-curious ``SecAggSession`` over the payload trees, the sim
    backend runs the dropout-robust session over the ciphertexts it actually
    gathered (including Shamir mask recovery).  Arms only ever say "sum
    these" — they never see masks, shares, or ciphertexts.
    """

    # When the backend ran the arm's fused round-step it may hand the
    # already-reduced cohort aggregate back to ``aggregate`` here (the
    # idealized backend with plain sums); ``None`` means "sum it yourself".
    fused_reduced: PyTree | None = None

    def sum_sizes(self, sizes: Sequence[int]) -> int:  # pragma: no cover
        raise NotImplementedError

    def sum_payloads(
        self, payloads: Mapping[int, PyTree]
    ) -> PyTree:  # pragma: no cover
        raise NotImplementedError


# -- arm base classes --------------------------------------------------------


class Arm:
    """Base for all arms.  Subclass ``RoundArm`` or ``NodeArm``, not this."""

    name: str = ""
    mode: str = ""                 # "round" | "node"
    private: bool = False          # has an accountant / nonzero epsilon
    topology_kind: str = "full"    # natural sim topology: full | star | ring

    def __init__(
        self,
        model: Model,
        participants: Sequence[Participant],
        cfg: ArmConfig,
    ) -> None:
        if not participants:
            raise ValueError("need at least one participant")
        self.model = model
        self.participants = list(participants)
        self.cfg = cfg
        self.h = len(self.participants)

    # Privacy interface (shared by both modes).
    def epsilon(self) -> float:
        return 0.0

    def should_stop(self) -> bool:
        """Budget exceeded — the backend stops scheduling further rounds."""
        return False


class RoundArm(Arm):
    """Synchronous-round arm: contribute -> aggregate -> broadcast.

    The backend owns the cohort (who is online / eligible), the transport
    (free vs simulated), and the secure-sum transcript; the arm owns every
    number that ends up in the model.
    """

    mode = "round"
    secure_uploads = False        # payloads go through SecAgg when enabled
    requires_dst_online = False   # star hub must survive the whole round
    void_logs = False             # log a NaN round when nothing aggregates
    empty_break = False           # empty cohort ends the run (vs skipping)
    fused_capable = False         # overrides fused_round (backend capability
                                  # negotiation: fused-only backends refuse
                                  # arms without it)
    distributed_noise = False     # DP noise rides per-participant shares, so
                                  # a lost upload under-noises the sum (the
                                  # backend owes a top-up — DESIGN.md §10)

    def round_cost(self, i: int) -> int:
        """Expected examples participant ``i`` processes in one round (the
        trace phase's compute-time model; actual draws happen at solve)."""
        return min(self.cfg.batch_size, len(self.participants[i]))

    def clipped_grad_sum_fn(self, pad: int):
        """Model-aware clipped-grad-sum seam (DESIGN.md §12).

        Returns ``fn(params, {"x", "y"}, mask) -> (grad_sum, loss)``: the
        ghost path for models declaring the capability, the faithful
        ``dp.per_example_clipped_grad_sum`` otherwise — resolved once at arm
        construction so the choice is visible in ``clipping_path``.
        """
        from repro.arms import clipping as clipping_lib

        self.clipping_path = clipping_lib.resolve(self.model, self.cfg)
        return clipping_lib.clipped_grad_sum_fn(self.model, self.cfg, pad)

    # --- cohort / schedule ---------------------------------------------------

    def planned_rounds(self) -> int:
        """Idealized-backend round cap (e.g. pre-computed epsilon budget)."""
        return self.cfg.rounds

    def quorum(self) -> tuple[int, int | None]:
        """(minimum online nodes, required node index or None) to start."""
        return 1, None

    def participates(self, i: int, t: int) -> bool:
        """Eligibility beyond availability (e.g. local budget exhausted)."""
        return True

    def facilitator(self, t: int, active: Sequence[int]) -> int:
        """Who aggregates round ``t`` given the active cohort."""
        raise NotImplementedError

    # --- numerics ------------------------------------------------------------

    def init_params(self) -> PyTree:
        return self.model.init_fn(jax.random.key(self.cfg.seed))

    def contribution(
        self,
        params: PyTree,
        i: int,
        t: int,
        rng: np.random.Generator,
        n_shares: int,
    ) -> Contribution | None:
        """Participant ``i``'s upload for round ``t`` (None = sits out)."""
        raise NotImplementedError

    def fused_round(
        self,
        params: PyTree,
        active: Sequence[int],
        t: int,
        rng: np.random.Generator,
        n_shares: int,
        need_payloads: bool,
        need_reduced: bool = True,
    ) -> tuple[dict[int, Contribution], PyTree | None] | None:
        """The cohort-batched hot path (DESIGN.md §7): every active
        participant's contribution in ONE jit dispatch with ONE host sync
        for metrics, plus (optionally) the in-jit reduced cohort aggregate.

        Must consume ``rng`` exactly as the ``contribution()`` loop would.
        Return ``None`` to fall back to the per-participant loop (the
        default — arms opt in).  ``need_payloads=False`` means the backend
        will not read per-participant payloads and they may be withheld
        (stay on device, never unstacked); ``need_reduced=False`` means the
        backend will sum the payloads itself (sim transport, SecAgg) and
        the in-jit reduction may be skipped.  The second element is the
        reduced tree, or — with neither asked for — the (n, ...) payload
        stack on the device (``fused.cohort_output``), which the idealized
        backend's SecAgg masks and sums there.
        """
        return None

    def aggregate(
        self,
        params: PyTree,
        contributions: Mapping[int, Contribution],
        services: AggregationServices,
    ) -> RoundOutcome:
        raise NotImplementedError

    def account(self) -> None:
        """Advance the accountant after a stepped round (no-op by default)."""


class NodeArm(Arm):
    """Per-node arm: independent models, local steps, optional gossip mixing.

    The backend drives the step loop (lockstep when idealized, event-ordered
    under simulated time) and performs the pairwise model averaging; the arm
    owns the local update and the exchange cadence/peer choice.
    """

    mode = "node"
    topology_kind = "ring"

    def steps_total(self) -> int:
        return self.cfg.gossip_steps or self.cfg.rounds

    def step_cost(self, i: int) -> int:
        """Examples one local step processes (sim compute-time model)."""
        return min(self.cfg.batch_size, len(self.participants[i]))

    def init_node_params(self, i: int) -> PyTree:
        raise NotImplementedError

    def local_step(
        self, i: int, params_i: PyTree, s: int
    ) -> tuple[PyTree, float, int] | None:
        """One local step; (new params, loss, examples) or None = retired."""
        raise NotImplementedError

    def wants_exchange(self, i: int, steps_done: int) -> bool:
        return False

    def select_peer(self, i: int, neighbors: Sequence[int]) -> int | None:
        return None

    def consensus(
        self, per_node_params: list[PyTree]
    ) -> tuple[PyTree, list[PyTree]]:
        """(headline params, per-node params) once every node finished."""
        return per_node_params[0], per_node_params
