"""FL — the paper's non-private comparison arm (FedSGD / FedAvg).

``fl_local_steps == 1`` is FedSGD with DeCaPH's sampling/sync cadence (the
paper's FL arm; SL is equivalent for utility); ``> 1`` is FedAvg (McMahan et
al.): each client takes k local SGD steps per round and the server
size-weights the resulting weights.
"""

from __future__ import annotations

from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

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


@register("fl")
class FLArm(RoundArm):
    """Server-based FL without DP (utility upper bound)."""

    requires_dst_online = True    # classic single point of failure
    topology_kind = "star"
    fused_capable = True

    def __init__(self, model: Model, participants: Sequence[Participant],
                 cfg: ArmConfig) -> None:
        super().__init__(model, participants, cfg)
        n_total = sum(len(p) for p in self.participants)
        self.rate = cfg.batch_size / n_total
        self.pad = default_pad(self.rate, self.participants, cfg)
        self.fedavg = cfg.fl_local_steps > 1

        def batch_grad(p, b, m):
            def masked_loss(pp):
                losses = jax.vmap(lambda ex: model.loss_fn(pp, ex))(b)
                return jnp.sum(losses * m)
            return jax.grad(masked_loss)(p)

        self._batch_grad_raw = batch_grad
        self._batch_grad = fused.instrumented_jit(batch_grad)

        def cohort_sgd(params, bx, by, masks):
            """FedSGD: every client's masked-sum gradient + the cohort
            total, one program."""
            stack = jax.vmap(
                lambda bx_i, by_i, m_i: batch_grad(
                    params, {"x": bx_i, "y": by_i}, m_i
                )
            )(bx, by, masks)
            return stack, fused.seq_tree_sum(stack, bx.shape[0])

        def cohort_avg(params, bx, by, masks, counts, weights):
            """FedAvg-family: every client's K local steps (scan) + the
            size-weighted average, one program.  Empty Poisson draws skip
            the step exactly like the loop path's ``continue``."""

            def one(bxs, bys, ms, ks):
                def step(local, inp):
                    bx_i, by_i, m_i, k_i = inp
                    g = self._local_step_grad(
                        local, {"x": bx_i, "y": by_i}, m_i, k_i, params
                    )
                    new = sgd_update(local, g, cfg.lr, cfg.weight_decay)
                    new = jax.tree_util.tree_map(
                        lambda a, b: jnp.where(k_i > 0, a, b), new, local
                    )
                    return new, None

                local, _ = jax.lax.scan(step, params, (bxs, bys, ms, ks))
                return local

            stack = jax.vmap(one)(bx, by, masks, counts)
            return stack, fused.seq_weighted_sum(stack, weights, bx.shape[0])

        self._fused_sgd, self._fused_sgd_slim = \
            fused.instrumented_jit_pair(cohort_sgd)
        self._fused_avg, self._fused_avg_slim = \
            fused.instrumented_jit_pair(cohort_avg)

    def _local_step_grad(self, local, batch, mask, k, global_params):
        """One local step's gradient (FedProx overrides to add its proximal
        term).  ``k`` is the draw's real example count (traced int32)."""
        g = self._batch_grad_raw(local, batch, mask)
        return tree_div(g, jnp.maximum(k, 1))

    def _local_steps(self) -> int:
        return self.cfg.fl_local_steps

    def quorum(self) -> tuple[int, int | None]:
        # server-based FL stalls whenever the hub is offline
        return 1, self.cfg.fl_server

    def facilitator(self, t: int, active: Sequence[int]) -> int:
        return self.cfg.fl_server

    def contribution(self, params, i, t, rng, n_shares):
        part = self.participants[i]
        if not self.fedavg:  # FedSGD: one masked-sum gradient per client
            b, m, k = poisson_batch(rng, part, self.rate, self.pad)
            g = self._batch_grad(params, b, jnp.asarray(m))
            return Contribution(payload=g, size=k)
        # FedAvg: k local steps, upload the resulting weights
        local, consumed = params, 0
        for _ in range(self.cfg.fl_local_steps):
            b, m, k = poisson_batch(rng, part, self.rate, self.pad)
            if k == 0:
                continue
            g = self._batch_grad(local, b, jnp.asarray(m))
            g = tree_div(g, max(k, 1))
            local = sgd_update(local, g, self.cfg.lr, self.cfg.weight_decay)
            consumed += k
        return Contribution(payload=local, size=consumed)

    def fused_round(self, params, active, t, rng, n_shares, need_payloads,
                    need_reduced=True):
        if not self.fedavg:
            cb = fused.stack_poisson(
                rng, self.participants, active, self.rate, self.pad
            )
            if need_reduced:
                stack, reduced = self._fused_sgd(params, cb.x, cb.y, cb.masks)
            else:
                (stack,) = self._fused_sgd_slim(params, cb.x, cb.y, cb.masks)
                reduced = None
            return fused.build_contributions(
                active, stack, None, cb.sizes, need_payloads
            ), fused.cohort_output(stack, reduced, need_payloads)
        cb = fused.stack_poisson(
            rng, self.participants, active, self.rate, self.pad,
            steps=self._local_steps(),
        )
        # f32 weights now so the in-jit weighted sum multiplies by exactly
        # the scalars the eager size-weighted average would
        sizes = [float(len(self.participants[i])) for i in active]
        wsum = sum(sizes)
        weights = np.asarray([w / wsum for w in sizes], np.float32)
        args = (params, cb.x, cb.y, cb.masks, cb.counts, weights)
        if need_reduced:
            stack, reduced = self._fused_avg(*args)
        else:
            (stack,), reduced = self._fused_avg_slim(*args), None
        return fused.build_contributions(
            active, stack, None, cb.sizes, need_payloads
        ), fused.cohort_output(stack, reduced, need_payloads)

    def aggregate(
        self,
        params,
        contributions: Mapping[int, Contribution],
        services: AggregationServices,
    ) -> RoundOutcome:
        order = sorted(contributions)
        if not order:
            return RoundOutcome(params, stepped=False)
        if self.fedavg:  # size-weighted weight averaging
            if services.fused_reduced is not None:
                # the fused program already holds the weighted average
                return RoundOutcome(services.fused_reduced, stepped=True,
                                    aggregate_batch=self.cfg.batch_size)
            weights = [float(len(self.participants[i])) for i in order]
            wsum = sum(weights)
            params = jax.tree_util.tree_map(
                lambda *xs: sum(w / wsum * x for w, x in zip(weights, xs)),
                *[contributions[i].payload for i in order],
            )
            return RoundOutcome(params, stepped=True,
                                aggregate_batch=self.cfg.batch_size)
        agg = services.sum_sizes([contributions[i].size for i in order])
        if agg == 0:
            return RoundOutcome(params, stepped=False)
        total = services.sum_payloads(
            {i: contributions[i].payload for i in order}
        )
        grad = tree_div(total, agg)
        params = sgd_update(params, grad, self.cfg.lr, self.cfg.weight_decay)
        return RoundOutcome(params, stepped=True, aggregate_batch=agg)
