"""Continuous-batching serving engine (DESIGN.md §9).

A fixed-slot decode batch with per-slot KV-cache lifecycle:

  * **admit**: a request gets a free slot; its prompt is prefilled by ONE
    jitted program (``tf.prefill`` — a scan of the decode step, exact for
    every mixer family) which also samples the first generated token, and
    the prefilled per-request cache is spliced into the running batch cache
    by one more program (``dynamic_update_slice`` along the slot axis);
  * **decode**: one jitted program per step for the WHOLE batch —
    ``tf.decode_step_positions`` advances every slot at its own sequence
    position and the next token is sampled in-jit, so steady state is
    exactly 1 program launch + 1 host sync per token regardless of
    arrival/completion churn (the ``instrumented_jit`` counter certifies
    this in tests and CI, the same invariant DESIGN.md §7 pins for fused
    training rounds);
  * **evict**: EOS / token budget / context exhaustion frees the slot —
    pure host bookkeeping, zero dispatches; the stale KV rows are inert
    (free slots decode a dummy token but nothing reads their output) and
    are fully overwritten by the next admission's splice.

Params are just an argument to the decode program: hot-swapping a newly
published federation checkpoint (``handoff.CheckpointWatcher``) between
steps changes no shapes, triggers no recompile, and never touches the KV
cache — in-flight generations simply continue under the new weights.

Attention archs route single-query attention through the
``decode_attention`` kernel (Pallas on TPU, oracle elsewhere) via
``use_decode_kernel``.  MoE archs note: per-slot decode routes experts
with per-row capacity (no cross-request routing interference), which
deviates from aligned-batch ``decode_step`` at the dropped-token level.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

import repro.obs as obs
from repro.configs import get_config, get_smoke_config
from repro.instrument import instrumented_jit
from repro.kernels.decode_attention.kernel import DEFAULT_BLOCK_K
from repro.models import transformer as tf
from repro.serve.traffic import Request

PyTree = Any


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine knobs (the model itself comes from ``arch``/``model_cfg``)."""

    arch: str = "smollm-360m"
    slots: int = 4                 # fixed decode-batch width
    max_len: int = 96              # per-slot KV capacity (prompt + generation)
    temperature: float = 1.0       # 0 = greedy
    eos_id: int | None = None      # None = budget-only termination
    seed: int = 0
    smoke: bool = True             # smoke-scale model config
    decode_kernel: bool = True     # route attn through decode_attention


@dataclasses.dataclass
class _Slot:
    request: Request
    position: int                  # next KV write index
    token: int                     # last sampled token (next step's input)
    emitted: int                   # generated tokens so far


class ServeEngine:
    """Fixed-slot continuous batching over any decoder-only arch."""

    def __init__(self, cfg: ServeConfig, *, model_cfg=None,
                 params: PyTree | None = None, round_idx: int = -1) -> None:
        self.cfg = cfg
        block = DEFAULT_BLOCK_K
        if cfg.decode_kernel and cfg.max_len > block and cfg.max_len % block:
            raise ValueError(
                f"max_len={cfg.max_len}: the decode_attention kernel reads the "
                f"KV cache in tiles of {block}; use max_len <= {block} or a "
                f"multiple of {block}"
            )
        if model_cfg is None:
            model_cfg = (get_smoke_config(cfg.arch) if cfg.smoke
                         else get_config(cfg.arch))
        if model_cfg.is_encoder_decoder:
            raise ValueError(
                f"{model_cfg.name}: encoder-decoder archs need an encoder "
                "pass per request; the serving tier is decoder-only"
            )
        if cfg.decode_kernel:
            model_cfg = model_cfg.replace(use_decode_kernel=True)
        self.model_cfg = model_cfg
        self.params = (params if params is not None
                       else tf.init(model_cfg, jax.random.key(cfg.seed)))
        self.serving_round = round_idx   # -1 = seed weights, else ckpt round
        self.swaps = 0

        self.slots: list[_Slot | None] = [None] * cfg.slots
        self.cache = tf.init_cache(model_cfg, cfg.slots, cfg.max_len)
        self._key = jax.random.key(cfg.seed + 1)
        self._step_counter = 0
        self._admit_counter = 0
        # engine-local dispatch bookkeeping (the process-global counter in
        # ``repro.instrument`` also ticks; these let a harness attribute
        # launches to decode vs admission even when training shares the
        # process)
        self.decode_steps = 0
        self.decode_dispatches = 0
        self.admit_dispatches = 0

        mcfg, temp, max_len = model_cfg, cfg.temperature, cfg.max_len

        def _sample(logits, key):
            lg = logits[:, -1].astype(jnp.float32)
            if temp > 0:
                return jax.random.categorical(key, lg / temp, axis=-1
                                              ).astype(jnp.int32)
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)

        def decode_fn(params, cache, tokens, positions, key):
            logits, cache = tf.decode_step_positions(
                mcfg, params, cache, tokens, positions
            )
            return _sample(logits, key), cache

        def prefill_fn(params, tokens, key):
            cache = tf.init_cache(mcfg, 1, max_len)
            logits, cache = tf.prefill(mcfg, params, cache, tokens)
            return _sample(logits, key), cache

        def insert_fn(cache, slot_cache, slot):
            return jax.tree_util.tree_map(
                lambda big, one: jax.lax.dynamic_update_slice_in_dim(
                    big, one.astype(big.dtype), slot, axis=1
                ),
                cache, slot_cache,
            )

        # exactly one program launch per steady-state decode step; admission
        # costs two (prefill + slot splice), amortised over the request
        self._decode = instrumented_jit(decode_fn, donate_argnums=(1,))
        self._prefill = instrumented_jit(prefill_fn)
        self._insert = instrumented_jit(insert_fn, donate_argnums=(0,))

    # -- params / handoff -----------------------------------------------------

    def set_params(self, params: PyTree, round_idx: int) -> None:
        """Hot-swap weights between decode steps.  Same pytree shapes ->
        same compiled programs; in-flight generations keep their KV cache
        and continue under the new params."""
        self.params = params
        self.serving_round = round_idx
        self.swaps += 1
        obs.counter("serve.swaps", 1, round=round_idx)

    def poll_watcher(self, watcher) -> bool:
        """Swap in the newest published checkpoint, if any.  True on swap."""
        got = watcher.poll()
        if got is None:
            return False
        params, round_idx, _meta = got
        self.set_params(params, round_idx)
        return True

    # -- slot lifecycle -------------------------------------------------------

    def free_slots(self) -> int:
        return sum(s is None for s in self.slots)

    def active_count(self) -> int:
        return sum(s is not None for s in self.slots)

    def busy(self) -> bool:
        return any(s is not None for s in self.slots)

    def admit(self, request: Request, now: float = 0.0) -> bool:
        """Prefill ``request`` into a free slot.  Returns True if the
        request already finished at admission (1-token budget or instant
        EOS) — it then never occupies the slot."""
        if len(request.prompt) + 1 > self.cfg.max_len:
            raise ValueError(
                f"request {request.rid}: prompt of {len(request.prompt)} "
                f"tokens leaves no room to generate within max_len="
                f"{self.cfg.max_len}"
            )
        idx = next(i for i, s in enumerate(self.slots) if s is None)
        key = jax.random.fold_in(self._key, (self._admit_counter << 1) | 1)
        self._admit_counter += 1
        tokens = jnp.asarray(request.prompt, jnp.int32)[None]
        with obs.span("serve.admit", cat="serve", rid=request.rid,
                      prompt=len(request.prompt), slot=idx):
            tok0, slot_cache = self._prefill(self.params, tokens, key)
            self.cache = self._insert(self.cache, slot_cache,
                                      jnp.asarray(idx, jnp.int32))
            tok0 = int(np.asarray(tok0)[0])
        self.admit_dispatches += 2
        request.t_admit = request.t_first = now
        request.round_at_first = self.serving_round
        request.tokens.append(tok0)
        budget = self._budget(request)
        if tok0 == self.cfg.eos_id or len(request.tokens) >= budget:
            request.t_done = now
            return True
        self.slots[idx] = _Slot(request, position=len(request.prompt),
                                token=tok0, emitted=1)
        return False

    def _budget(self, request: Request) -> int:
        """Generation budget: the request's ask, clamped to KV capacity."""
        return min(request.max_new_tokens,
                   self.cfg.max_len - len(request.prompt))

    def step(self, now: float = 0.0) -> list[Request]:
        """One decode step for the whole batch: 1 dispatch + 1 host sync.
        Returns the requests that finished this step (their slots are
        freed — pure host bookkeeping, no extra dispatch)."""
        tokens = np.zeros((self.cfg.slots, 1), np.int32)
        positions = np.zeros((self.cfg.slots,), np.int32)
        for i, s in enumerate(self.slots):
            if s is not None:
                tokens[i, 0] = s.token
                positions[i] = s.position
        key = jax.random.fold_in(self._key, self._step_counter << 1)
        self._step_counter += 1
        # span covers the dispatch AND the host sync: together they are the
        # per-token latency the metrics layer reports as TPOT
        with obs.span("serve.decode_step", cat="serve",
                      active=self.active_count()):
            nxt, self.cache = self._decode(
                self.params, self.cache, tokens, positions, key
            )
            nxt = np.asarray(nxt)  # the single per-token host sync
        self.decode_steps += 1
        self.decode_dispatches += 1
        finished: list[Request] = []
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            tok = int(nxt[i])
            s.request.tokens.append(tok)
            s.position += 1
            s.token = tok
            s.emitted += 1
            if (tok == self.cfg.eos_id
                    or s.emitted >= self._budget(s.request)
                    or s.position + 1 > self.cfg.max_len):
                s.request.t_done = now
                finished.append(s.request)
                self.slots[i] = None   # evict: host bookkeeping only
        if finished:
            obs.counter("serve.evictions", len(finished))
        return finished


def batch_generate(engine: ServeEngine, prompts: np.ndarray, gen: int
                   ) -> np.ndarray:
    """Static-batch convenience used by the ``launch/serve`` shim: admit
    ``B <= slots`` equal-length prompts, decode until every request has
    ``gen`` tokens.  Returns the generated tokens [B, gen]."""
    b = prompts.shape[0]
    if b > engine.cfg.slots:
        raise ValueError(f"{b} prompts > {engine.cfg.slots} slots")
    requests = [
        Request(rid=i, arrival=0.0, prompt=np.asarray(prompts[i], np.int32),
                max_new_tokens=gen)
        for i in range(b)
    ]
    pending = [r for r in requests if not engine.admit(r)]
    while pending:
        done = engine.step()
        pending = [r for r in pending if r not in done]
    return np.stack([np.asarray(r.tokens[:gen], np.int64) for r in requests])
