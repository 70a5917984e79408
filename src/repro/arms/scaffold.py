"""SCAFFOLD (Karimireddy et al., 2020) — control-variate FedAvg, as an arm.

FedAvg drifts under heterogeneous silos: each client's local steps descend
its *local* loss, so the averaged model is pulled toward client optima.
SCAFFOLD corrects every local step with control variates — ``c`` (server)
and ``c_i`` (per client) estimating the global vs local update direction:

    y  <-  y - lr * (g_i(y) - c_i + c)

After K local steps the client uploads the model delta and its control
delta (Option II of the paper):

    dy  = y_K - x
    c_i+ = c_i - c + (x - y_K) / (K * lr)      =>   dc = c_i+ - c_i

and the server applies ``x += mean(dy)``, ``c += (|S|/N) * mean(dc)``.

Registered once (DESIGN.md §5): both backends, the CLI smoke matrix, the
scenario sweep axes and the CI jobs pick it up with zero further wiring —
and it rides the fused cohort round-step (DESIGN.md §7), carrying its
per-client control variates through the one-dispatch program as a stacked
``(H, ...)`` pytree.
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
    sgd_update,
    tree_div,
)
from repro.arms import fused
from repro.arms.registry import register


@register("scaffold")
class ScaffoldArm(RoundArm):
    """Control-variate FedAvg: heterogeneity-robust server-based FL."""

    requires_dst_online = True    # classic single point of failure
    topology_kind = "star"
    fused_capable = True

    def __init__(self, model: Model, participants: Sequence[Participant],
                 cfg: ArmConfig) -> None:
        super().__init__(model, participants, cfg)
        n_total = sum(len(p) for p in self.participants)
        self.rate = cfg.batch_size / n_total
        self.pad = default_pad(self.rate, self.participants, cfg)
        # SCAFFOLD only differs from FedSGD when clients take several steps
        self.local_steps = max(2, cfg.fl_local_steps)
        template = model.init_fn(jax.random.key(cfg.seed))
        self._c = jax.tree_util.tree_map(jnp.zeros_like, template)
        # per-client variates as one stacked (H, ...) tree: the fused
        # program gathers the active rows, steps them, and scatters the
        # updated rows back — all inside the round's single dispatch
        self._ci = jax.tree_util.tree_map(
            lambda x: jnp.zeros((self.h,) + x.shape, x.dtype), template
        )

        def batch_grad(p, b, m):
            def masked_loss(pp):
                losses = jax.vmap(lambda ex: model.loss_fn(pp, ex))(b)
                return jnp.sum(losses * m)
            return jax.grad(masked_loss)(p)

        def one_client(params, c, ci, bxs, bys, ms, ks):
            """K corrected local steps for one client; empty draws skipped."""

            def step(local, inp):
                bx_i, by_i, m_i, k_i = inp
                g = tree_div(batch_grad(local, {"x": bx_i, "y": by_i}, m_i),
                             jnp.maximum(k_i, 1))
                g = jax.tree_util.tree_map(
                    lambda gl, cs, cl: gl + cs - cl, g, c, ci
                )
                new = sgd_update(local, g, cfg.lr, cfg.weight_decay)
                new = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(k_i > 0, a, b), new, local
                )
                return new, None

            local, _ = jax.lax.scan(step, params, (bxs, bys, ms, ks))
            dy = jax.tree_util.tree_map(jnp.subtract, local, params)
            inv_klr = 1.0 / (self.local_steps * cfg.lr)
            dc = jax.tree_util.tree_map(
                lambda cs, d: -cs - inv_klr * d, c, dy
            )
            return {"dy": dy, "dc": dc}

        self._one_client = fused.instrumented_jit(one_client)

        def cohort_step(params, c, ci_stack, bx, by, masks, counts, idxs):
            ci_rows = jax.tree_util.tree_map(lambda x: x[idxs], ci_stack)
            stack = jax.vmap(
                one_client, in_axes=(None, None, 0, 0, 0, 0, 0)
            )(params, c, ci_rows, bx, by, masks, counts)
            ci_new = jax.tree_util.tree_map(
                lambda st, rows, d: st.at[idxs].set(rows + d),
                ci_stack, ci_rows, stack["dc"],
            )
            return stack, fused.seq_tree_sum(stack, bx.shape[0]), ci_new

        # the per-client variate stack is the one buffer an output can
        # alias: ci_new has ci_stack's exact shape, so donation makes the
        # scatter-update effectively in-place across rounds
        self._fused_step, self._fused_step_slim = fused.instrumented_jit_pair(
            cohort_step, donate_argnums=(2,)
        )

    def quorum(self) -> tuple[int, int | None]:
        return 1, self.cfg.fl_server

    def facilitator(self, t: int, active: Sequence[int]) -> int:
        return self.cfg.fl_server

    # --- numerics ------------------------------------------------------------

    def contribution(self, params, i, t, rng, n_shares):
        cb = fused.stack_poisson(
            rng, self.participants, [i], self.rate, self.pad,
            steps=self.local_steps,
        )
        ci = jax.tree_util.tree_map(lambda x: x[i], self._ci)
        payload = self._one_client(
            params, self._c, ci, cb.x[0], cb.y[0], cb.masks[0], cb.counts[0]
        )
        self._ci = jax.tree_util.tree_map(
            lambda st, cl, d: st.at[i].set(cl + d),
            self._ci, ci, payload["dc"],
        )
        return Contribution(payload=payload, size=cb.sizes[0])

    def fused_round(self, params, active, t, rng, n_shares, need_payloads,
                    need_reduced=True):
        cb = fused.stack_poisson(
            rng, self.participants, active, self.rate, self.pad,
            steps=self.local_steps,
        )
        args = (params, self._c, self._ci, cb.x, cb.y, cb.masks, cb.counts,
                np.asarray(active, np.int32))
        if need_reduced:
            stack, reduced, self._ci = self._fused_step(*args)
        else:
            (stack, self._ci), reduced = self._fused_step_slim(*args), None
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
        n = len(order)
        total = services.sum_payloads(
            {i: contributions[i].payload for i in order}
        )
        mean_dy = tree_div(total["dy"], n)
        mean_dc = tree_div(total["dc"], n)
        params = jax.tree_util.tree_map(jnp.add, params, mean_dy)
        self._c = jax.tree_util.tree_map(
            lambda cs, d: cs + (n / self.h) * d, self._c, mean_dc
        )
        agg = int(sum(contributions[i].size for i in order))
        return RoundOutcome(params, stepped=True,
                            aggregate_batch=agg or self.cfg.batch_size)
