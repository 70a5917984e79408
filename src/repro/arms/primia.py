"""PriMIA-style local-DP FL as a registered arm.

Every client runs its own DP-SGD: local Poisson rate ``B_h / |D_h|``, the
FULL noise N(0, (C sigma)^2) added locally (n_shares=1), and a *local*
accountant.  A client stops contributing once another step would overshoot
its own epsilon budget — clients with higher sampling rates (small silos)
drop out first, the forgetting failure mode the paper describes.
"""

from __future__ import annotations

from typing import Mapping, Sequence

import jax
import numpy as np

from repro.arms.base import (
    AggregationServices,
    ArmConfig,
    Contribution,
    Model,
    Participant,
    RoundArm,
    RoundOutcome,
    poisson_batch,
    sgd_update,
    tree_div,
)
from repro.arms import fused
from repro.arms.registry import register
from repro.core import dp as dp_lib
from repro.core.accountant import RDPAccountant, steps_for_epsilon

_NOISE_SALT = 31  # legacy key derivation: fold_in(fold_in(key, 31 + t), i)


@register("primia")
class PriMIAArm(RoundArm):
    """Local-DP FL through a star hub, per-client accountants."""

    private = True
    requires_dst_online = True
    empty_break = True            # every budget exhausted -> run over
    topology_kind = "star"
    fused_capable = True

    def __init__(self, model: Model, participants: Sequence[Participant],
                 cfg: ArmConfig) -> None:
        super().__init__(model, participants, cfg)
        per_client_batch = max(1, cfg.batch_size // self.h)
        self.rates = [
            min(1.0, per_client_batch / max(len(p), 1))
            for p in self.participants
        ]
        self.pads = [
            cfg.max_pad_batch or max(8, int(r * len(p) * 4) or 8)
            for r, p in zip(self.rates, self.participants)
        ]
        self.accts = [
            RDPAccountant(sampling_rate=r,
                          noise_multiplier=cfg.dp.noise_multiplier,
                          delta=cfg.dp.delta)
            for r in self.rates
        ]
        if cfg.epsilon_budget is not None:
            # a client only participates while ANOTHER step stays within its
            # local budget (never overshoots)
            self.max_rounds = [
                steps_for_epsilon(r, cfg.dp.noise_multiplier,
                                  cfg.epsilon_budget, cfg.dp.delta,
                                  max_steps=cfg.rounds + 1)
                for r in self.rates
            ]
        else:
            self.max_rounds = [cfg.rounds] * self.h
        self._key = jax.random.key(cfg.seed)
        # Same clipped-grad-sum seam as decaph (DESIGN.md §12); the pad hint
        # only caps the faithful path's microbatch, so keep the configured
        # microbatch size by passing the largest per-client pad.
        clip_fn = self.clipped_grad_sum_fn(
            max(cfg.dp.microbatch_size, *self.pads)
        )
        self._clipped_sum = fused.instrumented_jit(
            lambda p, b, m: clip_fn(p, b, m)
        )

        def cohort_step(params, bx, by, masks, counts, salt_t, idxs):
            """Every client's locally-noised mean gradient + the cohort
            total in one program.  The ragged per-client Poisson draws ride
            the cohort pad (padded to the round max; masks keep the extra
            rows inert), noise keys fold in ``(salt_t, idx)`` exactly like
            the per-participant path, and each client divides by its own
            real-example count."""

            def one(bx_i, by_i, m_i, k_i, idx):
                g_sum, loss = clip_fn(params, {"x": bx_i, "y": by_i}, m_i)
                nkey = jax.random.fold_in(
                    jax.random.fold_in(self._key, salt_t), idx
                )
                # Local DP: the FULL noise per client (n_shares=1).
                g = dp_lib.tree_add_noise(
                    g_sum, nkey, clip_norm=cfg.dp.clip_norm,
                    noise_multiplier=cfg.dp.noise_multiplier, n_shares=1,
                )
                g = jax.tree_util.tree_map(
                    lambda x: x / jax.numpy.maximum(k_i, 1), g
                )
                return g, loss

            stack, losses = jax.vmap(one)(bx, by, masks, counts, idxs)
            return stack, fused.seq_tree_sum(stack, bx.shape[0]), losses

        self._fused_step, self._fused_step_slim = fused.instrumented_jit_pair(
            cohort_step
        )

    def quorum(self) -> tuple[int, int | None]:
        return 1, self.cfg.fl_server

    def participates(self, i: int, t: int) -> bool:
        return self.accts[i].steps < self.max_rounds[i]

    def facilitator(self, t: int, active: Sequence[int]) -> int:
        return self.cfg.fl_server

    def contribution(self, params, i, t, rng, n_shares):
        b, m, k = poisson_batch(
            rng, self.participants[i], self.rates[i], self.pads[i]
        )
        g_sum, loss = self._clipped_sum(params, b, jax.numpy.asarray(m))
        nkey = jax.random.fold_in(
            jax.random.fold_in(self._key, _NOISE_SALT + t), i
        )
        # Local DP: the FULL noise per client (n_shares=1).
        g = dp_lib.tree_add_noise(
            g_sum, nkey, clip_norm=self.cfg.dp.clip_norm,
            noise_multiplier=self.cfg.dp.noise_multiplier, n_shares=1,
        )
        g = tree_div(g, max(k, 1))
        self.accts[i].step()  # privacy is spent at compute time, not arrival
        return Contribution(payload=g, size=k, loss=float(loss))

    def fused_round(self, params, active, t, rng, n_shares, need_payloads,
                    need_reduced=True):
        # per-client rates *and* pads: the stack draws each client with its
        # own (rate, pad) in loop order, then re-pads to the cohort max
        cb = fused.stack_poisson(
            rng, self.participants, active, self.rates, self.pads
        )
        args = (params, cb.x, cb.y, cb.masks, cb.counts,
                np.int32(_NOISE_SALT + t), np.asarray(active, np.int32))
        if need_reduced:
            stack, reduced, losses = self._fused_step(*args)
        else:
            (stack, losses), reduced = self._fused_step_slim(*args), None
        for i in active:
            self.accts[i].step()  # spent at compute time, like the loop path
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
        if not order:
            return RoundOutcome(params, stepped=False)
        total = services.sum_payloads(
            {i: contributions[i].payload for i in order}
        )
        grad = tree_div(total, len(order))
        params = sgd_update(params, grad, self.cfg.lr, self.cfg.weight_decay)
        agg = int(sum(contributions[i].size for i in order))
        return RoundOutcome(params, stepped=True, aggregate_batch=agg)

    def epsilon(self) -> float:
        return max(a.epsilon() for a in self.accts)
