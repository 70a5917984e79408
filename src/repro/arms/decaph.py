"""DeCaPH — the paper's framework, Steps 1-7, as a registered arm.

Shared Poisson rate, per-example clipping, per-participant noise shares
sized so the secure **sum** carries N(0, (C sigma)^2), SecAgg aggregation,
rotating facilitator, one shared RDP accountant over the aggregate dataset.
"""

from __future__ import annotations

from typing import Mapping, Sequence

import jax
import numpy as np

import repro.obs as obs
from repro.arms.base import (
    AggregationServices,
    ArmConfig,
    Contribution,
    Model,
    Participant,
    RoundArm,
    RoundOutcome,
    default_pad,
    poisson_batch,
    sgd_update,
    tree_div,
)
from repro.arms import fused
from repro.arms.registry import register
from repro.core import dp as dp_lib
from repro.core.accountant import RDPAccountant, steps_for_epsilon
from repro.core.leader import leader_schedule

_NOISE_SALT = 17  # legacy key derivation: fold_in(fold_in(key, 17 + t), i)


@register("decaph")
class DeCaPHArm(RoundArm):
    """The DeCaPH protocol (distributed-noise DP-SGD behind SecAgg)."""

    private = True
    secure_uploads = True
    void_logs = True            # an empty Poisson round is logged as NaN
    topology_kind = "full"      # any participant can facilitate
    fused_capable = True
    distributed_noise = True    # per-participant noise shares sum to (Cσ)²

    def __init__(self, model: Model, participants: Sequence[Participant],
                 cfg: ArmConfig) -> None:
        super().__init__(model, participants, cfg)
        n_total = sum(len(p) for p in self.participants)
        self.rate = cfg.batch_size / n_total
        self.pad = default_pad(self.rate, self.participants, cfg)
        self.leaders = leader_schedule(
            self.h, cfg.rounds, seed=cfg.seed, strategy=cfg.leader_strategy
        )
        # With cohort subsampling (participation_rate q < 1) an example's
        # marginal inclusion probability per round is q * rate — hospital
        # Poisson at q, then example Poisson at rate inside sampled
        # hospitals — so the accountant composes at that product (see
        # population.sampler for why this stays an upper bound).
        self.acct = RDPAccountant(
            sampling_rate=self.rate * cfg.participation_rate,
            noise_multiplier=cfg.dp.noise_multiplier,
            delta=cfg.dp.delta,
        )
        self._key = jax.random.key(cfg.seed)
        # Model-aware clipped-grad-sum seam (DESIGN.md §12): ghost clipping
        # for dense decoder stacks declaring the capability, faithful
        # per-example clipping otherwise.  Noise, keys and accounting are
        # identical either way — the path only changes how the clipped sum
        # is computed.
        clip_fn = self.clipped_grad_sum_fn(self.pad)
        self._clipped_sum = fused.instrumented_jit(
            lambda p, b, m: clip_fn(p, b, m)
        )

        def cohort_step(params, bx, by, masks, salt_t, idxs, n_shares):
            """Every participant's noised clipped sum + the cohort total in
            one program; noise keys fold in ``(salt_t, idx)`` exactly as the
            per-participant path does, so batching changes no draw."""

            def one(bx_i, by_i, m_i, idx):
                g_sum, loss = clip_fn(params, {"x": bx_i, "y": by_i}, m_i)
                nkey = jax.random.fold_in(
                    jax.random.fold_in(self._key, salt_t), idx
                )
                noised = dp_lib.tree_add_noise(
                    g_sum, nkey, clip_norm=cfg.dp.clip_norm,
                    noise_multiplier=cfg.dp.noise_multiplier,
                    n_shares=n_shares,
                )
                return noised, loss

            stack, losses = jax.vmap(one)(bx, by, masks, idxs)
            return stack, fused.seq_tree_sum(stack, bx.shape[0]), losses

        def gathered_step(params, x_all, y_all, rows, masks, salt_t, idxs,
                          n_shares):
            """``cohort_step`` over the batch gathered here from the rows on
            the device (``fused.device_rows``): arguments, never constants."""
            return cohort_step(params, x_all[rows], y_all[rows], masks,
                               salt_t, idxs, n_shares)

        # the host-stacked program serves a mesh executor, which shards the
        # stacked batch along its example axis; the gathered one all else
        self._fused_step, self._fused_step_slim = fused.instrumented_jit_pair(
            cohort_step, static_argnums=(6,)
        )
        self._gather_step, self._gather_step_slim = (
            fused.instrumented_jit_pair(gathered_step, static_argnums=(7,)))
        self._rows = None  # (x, y) on the device, put there by the first round

    # --- schedule -------------------------------------------------------------

    def planned_rounds(self) -> int:
        if self.cfg.epsilon_budget is None:
            return self.cfg.rounds
        return min(
            self.cfg.rounds,
            steps_for_epsilon(
                self.rate * self.cfg.participation_rate,
                self.cfg.dp.noise_multiplier,
                self.cfg.epsilon_budget, self.cfg.dp.delta,
                max_steps=self.cfg.rounds + 1,
            ),
        )

    def quorum(self) -> tuple[int, int | None]:
        # Running below the configured reconstruction threshold would
        # silently weaken the operator's security choice.
        if self.cfg.use_secagg:
            return max(2, self.cfg.secagg_threshold or 2), None
        return 2, None

    def round_cost(self, i: int) -> int:
        # expected Poisson draw, not the full batch: at H=1000 a hospital
        # contributes rate * |shard| examples per round in expectation
        return max(1, int(round(self.rate * len(self.participants[i]))))

    def facilitator(self, t: int, active: Sequence[int]) -> int:
        leader = int(self.leaders[t])
        if leader in active:
            return leader
        # shared-seed schedule: everyone deterministically skips to the
        # next online hospital
        return active[t % len(active)]

    # --- numerics ---------------------------------------------------------------

    def contribution(self, params, i, t, rng, n_shares):
        b, m, k = poisson_batch(rng, self.participants[i], self.rate, self.pad)
        g_sum, loss = self._clipped_sum(params, b, jax.numpy.asarray(m))
        nkey = jax.random.fold_in(
            jax.random.fold_in(self._key, _NOISE_SALT + t), i
        )
        noised = dp_lib.tree_add_noise(
            g_sum, nkey, clip_norm=self.cfg.dp.clip_norm,
            noise_multiplier=self.cfg.dp.noise_multiplier, n_shares=n_shares,
        )
        return Contribution(payload=noised, size=k, loss=float(loss))

    def fused_round(self, params, active, t, rng, n_shares, need_payloads,
                    need_reduced=True):
        cb = fused.stack_poisson(
            rng, self.participants, active, self.rate, self.pad,
            host_stack=False,
        )
        if fused.active_executor() is not None:
            full, slim = self._fused_step, self._fused_step_slim
            batch = (cb.x, cb.y)
        else:
            if self._rows is None:
                self._rows = fused.device_rows(self.participants)
            full, slim = self._gather_step, self._gather_step_slim
            batch = (*self._rows, cb.rows)
        args = (params, *batch, cb.masks,
                np.int32(_NOISE_SALT + t), np.asarray(active, np.int32),
                n_shares)
        if need_reduced:
            stack, reduced, losses = full(*args)
        else:
            (stack, losses), reduced = slim(*args), None
        contribs = fused.build_contributions(
            active, stack, losses, cb.sizes, need_payloads
        )
        return contribs, fused.cohort_output(stack, reduced, need_payloads)

    def aggregate(
        self,
        params,
        contributions: Mapping[int, Contribution],
        services: AggregationServices,
    ) -> RoundOutcome:
        order = sorted(contributions)
        agg_batch = services.sum_sizes([contributions[i].size for i in order])
        if agg_batch == 0:
            return RoundOutcome(params, stepped=False)
        total = services.sum_payloads(
            {i: contributions[i].payload for i in order}
        )
        with obs.span("update", cat="train"):
            grad = tree_div(total, agg_batch)
            params = sgd_update(params, grad, self.cfg.lr,
                                self.cfg.weight_decay)
            loss = float(np.mean([contributions[i].loss for i in order]))
        return RoundOutcome(params, stepped=True, loss=loss,
                            aggregate_batch=agg_batch)

    # --- accounting -------------------------------------------------------------

    def account(self) -> None:
        self.acct.step()

    def epsilon(self) -> float:
        return self.acct.epsilon()

    def should_stop(self) -> bool:
        return (
            self.cfg.epsilon_budget is not None
            and self.acct.exceeds(self.cfg.epsilon_budget)
        )
