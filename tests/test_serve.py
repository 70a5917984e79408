"""repro.serve: dispatch-count invariant, hot swap, slot lifecycle, traffic.

The engine's structural promise — one jitted program launch + one host sync
per steady-state decode step, two launches per admission, zero per eviction
— is asserted against the process-global ``instrumented_jit`` meter (the
same one DESIGN.md §7 pins on fused training rounds).  Hot-swap tests pin
the handoff semantics: a published federation checkpoint is picked up
between steps and in-flight generations complete their full budget under
the new params.

Equivalence tests (prefill vs sequential decode, per-slot positional decode
vs aligned batch decode) use non-MoE archs: MoE expert capacity is computed
per row under the serving vmap (no cross-request routing interference),
which deviates from aligned-batch routing at the dropped-token level — a
documented serving semantic, not drift (see ``repro.serve.engine``).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.instrument import jit_dispatches, reset_jit_dispatches
from repro.models import transformer as tf
from repro.serve.engine import ServeConfig, ServeEngine, batch_generate
from repro.serve.handoff import (
    CheckpointPublisher,
    CheckpointWatcher,
    checkpoint_path,
    list_rounds,
)
from repro.serve.traffic import TrafficConfig, generate_requests, Request


def _engine(slots=2, max_len=32, temperature=1.0, eos_id=None, seed=0):
    return ServeEngine(ServeConfig(
        arch="smollm-360m", slots=slots, max_len=max_len,
        temperature=temperature, eos_id=eos_id, seed=seed,
    ))


def _request(rid=0, prompt_len=6, gen=8, fill=None):
    prompt = (np.full((prompt_len,), fill, np.int32) if fill is not None
              else np.arange(1, prompt_len + 1, dtype=np.int32))
    return Request(rid=rid, arrival=0.0, prompt=prompt, max_new_tokens=gen)


def test_engine_rejects_max_len_the_decode_kernel_cannot_tile():
    with pytest.raises(ValueError, match="tiles of 512"):
        _engine(max_len=600)
    assert _engine(slots=1, max_len=1024).cache is not None
    plain = ServeEngine(ServeConfig(slots=1, max_len=600, decode_kernel=False))
    assert plain.cfg.max_len == 600


# -- the O(1)-dispatch invariant ---------------------------------------------


def test_steady_state_is_one_dispatch_per_step():
    engine = _engine(slots=3, max_len=32)
    for i in range(3):
        assert not engine.admit(_request(rid=i, prompt_len=4, gen=20))
    reset_jit_dispatches()
    n = 10
    for _ in range(n):
        assert engine.step() == []   # nobody finishes inside the segment
    assert jit_dispatches() == n
    assert engine.decode_steps >= n
    assert engine.decode_dispatches == engine.decode_steps


def test_admission_costs_exactly_two_dispatches():
    engine = _engine(slots=2, max_len=32)
    reset_jit_dispatches()
    engine.admit(_request(rid=0, prompt_len=6, gen=8))
    assert jit_dispatches() == 2          # prefill + slot splice
    assert engine.admit_dispatches == 2


def test_eviction_is_dispatch_free():
    engine = _engine(slots=1, max_len=32, temperature=0.0)
    engine.admit(_request(rid=0, prompt_len=4, gen=2))
    reset_jit_dispatches()
    done = engine.step()                  # budget of 2 reached -> evict
    assert [r.rid for r in done] == [0]
    assert engine.free_slots() == 1
    assert jit_dispatches() == 1          # the decode step itself, nothing more


def test_churn_does_not_add_dispatches():
    # admissions and completions interleave, decode stays 1 launch/step
    engine = _engine(slots=2, max_len=32, temperature=0.0)
    engine.admit(_request(rid=0, prompt_len=4, gen=3))
    engine.admit(_request(rid=1, prompt_len=4, gen=30))
    total_steps = 0
    while engine.busy():
        before = engine.decode_dispatches
        done = engine.step()
        total_steps += 1
        assert engine.decode_dispatches == before + 1
        if done and engine.free_slots() and total_steps < 6:
            engine.admit(_request(rid=90 + total_steps, prompt_len=6, gen=2))
    assert engine.decode_dispatches == engine.decode_steps


# -- hot swap ----------------------------------------------------------------


def test_hot_swap_mid_stream_keeps_inflight_generations(tmp_path):
    engine = _engine(slots=2, max_len=32)
    reqs = [_request(rid=i, prompt_len=4, gen=10) for i in range(2)]
    for r in reqs:
        assert not engine.admit(r)
    for _ in range(3):
        engine.step()
    pub = CheckpointPublisher(str(tmp_path))
    watcher = CheckpointWatcher(str(tmp_path))
    pub.publish(5, jax.tree_util.tree_map(lambda x: x * 1.01, engine.params))
    assert engine.poll_watcher(watcher)
    assert engine.serving_round == 5 and engine.swaps == 1
    while engine.busy():
        engine.step()
    for r in reqs:
        assert len(r.tokens) == 10        # full budget, across the swap
        assert r.round_at_first == -1     # first token was pre-swap


def test_swap_changes_the_sampled_continuation(tmp_path):
    # same engine state, greedy sampling: stepping under swapped (scaled)
    # params is a REAL weight change, not a no-op
    def run(swap):
        engine = _engine(slots=1, max_len=32, temperature=0.0)
        r = _request(rid=0, prompt_len=6, gen=12)
        engine.admit(r)
        if swap:
            # rescaling final-norm/head changes logit sharpness -> greedy
            # path diverges eventually; cheaper than retraining
            engine.set_params(jax.tree_util.tree_map(
                lambda x: x * 0.5, engine.params), round_idx=1)
        while engine.busy():
            engine.step()
        return r.tokens

    base, swapped = run(False), run(True)
    assert len(base) == len(swapped) == 12
    assert base != swapped


def test_watcher_skips_corrupt_then_recovers(tmp_path):
    root = str(tmp_path)
    watcher = CheckpointWatcher(root)
    with open(checkpoint_path(root, 1), "wb") as f:
        f.write(b"torn to shreds")
    assert watcher.poll() is None         # skip, do not raise
    assert watcher.seen_round == -1       # not marked seen: retry allowed
    pub = CheckpointPublisher(root)
    pub.publish(2, {"w": jnp.ones((2,), jnp.float32)})
    got = watcher.poll()
    assert got is not None
    _, round_idx, _ = got
    assert round_idx == 2
    assert watcher.poll() is None         # nothing newer


def test_publisher_prunes_but_keeps_newest(tmp_path):
    pub = CheckpointPublisher(str(tmp_path), keep_last=2)
    for t in range(5):
        pub.publish(t, {"w": jnp.full((2,), float(t))})
    assert list_rounds(str(tmp_path)) == [3, 4]


# -- slot lifecycle ----------------------------------------------------------


def test_eos_evicts_early():
    # probe run: sampling is deterministic in (seed, admit/step counters),
    # so a fresh engine with the same seed reproduces the token stream and
    # we can pick a mid-stream token as the EOS id
    probe = _engine(slots=1, max_len=32, temperature=1.0, seed=11)
    r = _request(rid=0, prompt_len=4, gen=8)
    probe.admit(r)
    while probe.busy():
        probe.step()
    assert len(r.tokens) == 8
    k = next(i for i in range(1, 8) if r.tokens[i] != r.tokens[0])
    eos = r.tokens[k]

    engine = _engine(slots=1, max_len=32, temperature=1.0, seed=11,
                     eos_id=eos)
    r2 = _request(rid=0, prompt_len=4, gen=8)
    assert not engine.admit(r2)
    while engine.busy():
        engine.step()
    assert r2.tokens == r.tokens[:k + 1]  # stopped AT the eos token
    assert engine.free_slots() == 1


def test_budget_of_one_finishes_at_admission():
    engine = _engine(slots=1, max_len=32)
    r = _request(rid=0, prompt_len=4, gen=1)
    assert engine.admit(r)                # finished: never takes the slot
    assert engine.free_slots() == 1
    assert len(r.tokens) == 1 and r.t_done is not None


def test_prompt_exceeding_capacity_is_rejected():
    engine = _engine(slots=1, max_len=8)
    with pytest.raises(ValueError, match="no room to generate"):
        engine.admit(_request(rid=0, prompt_len=8, gen=4))


def test_generation_clamped_to_kv_capacity():
    engine = _engine(slots=1, max_len=12, temperature=0.0)
    r = _request(rid=0, prompt_len=8, gen=100)
    engine.admit(r)
    while engine.busy():
        engine.step()
    assert len(r.tokens) == 4             # max_len - prompt_len


def test_first_token_respects_temperature():
    # satellite-a regression: the FIRST generated token must be sampled at
    # --temperature like the rest, not argmax'd.  At temperature 1 two
    # different engine seeds must disagree on the first token for at least
    # one of several prompts (argmax would make them all identical).
    prompts = [np.full((4,), v, np.int32) for v in (3, 50, 200, 400, 17)]

    def first_tokens(seed):
        engine = _engine(slots=1, max_len=16, temperature=1.0, seed=seed)
        out = []
        for i, p in enumerate(prompts):
            r = Request(rid=i, arrival=0.0, prompt=p, max_new_tokens=1)
            engine.admit(r)
            out.append(r.tokens[0])
        return out

    a, b = first_tokens(0), first_tokens(9)
    assert a != b


# -- numerics: the engine's programs match the reference decode path ----------


@pytest.mark.parametrize("arch", ["smollm-360m", "rwkv6-3b"])
def test_prefill_matches_sequential_decode(arch):
    cfg = get_smoke_config(arch)
    key = jax.random.key(0)
    params = tf.init(cfg, key)
    b, s, max_len = 2, 7, 16
    toks = jax.random.randint(jax.random.fold_in(key, 1), (b, s), 0,
                              cfg.vocab_size)
    logits_p, cache_p = tf.prefill(cfg, params, tf.init_cache(cfg, b, max_len),
                                   toks)
    cache_s = tf.init_cache(cfg, b, max_len)
    for t in range(s):
        logits_s, cache_s = tf.decode_step(cfg, params, cache_s,
                                           toks[:, t:t + 1],
                                           jnp.asarray(t, jnp.int32))
    np.testing.assert_allclose(np.asarray(logits_p, np.float32),
                               np.asarray(logits_s, np.float32),
                               atol=2e-5, rtol=2e-5)
    for a, b_ in zip(jax.tree_util.tree_leaves(cache_p),
                     jax.tree_util.tree_leaves(cache_s)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b_, np.float32),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("arch", ["smollm-360m", "rwkv6-3b"])
def test_positional_decode_matches_aligned_decode(arch):
    # every slot at the SAME position must agree with the aligned batched
    # decode_step (per-slot positions generalize it)
    cfg = get_smoke_config(arch)
    key = jax.random.key(0)
    params = tf.init(cfg, key)
    b, s, max_len = 3, 5, 16
    toks = jax.random.randint(jax.random.fold_in(key, 1), (b, s + 1), 0,
                              cfg.vocab_size)
    _, cache = tf.prefill(cfg, params, tf.init_cache(cfg, b, max_len),
                          toks[:, :s])
    logits_a, cache_a = tf.decode_step(cfg, params, cache, toks[:, s:s + 1],
                                       jnp.asarray(s, jnp.int32))
    _, cache2 = tf.prefill(cfg, params, tf.init_cache(cfg, b, max_len),
                           toks[:, :s])
    logits_v, cache_v = tf.decode_step_positions(
        cfg, params, cache2, toks[:, s:s + 1],
        jnp.full((b,), s, jnp.int32))
    np.testing.assert_allclose(np.asarray(logits_a, np.float32),
                               np.asarray(logits_v, np.float32),
                               atol=2e-5, rtol=2e-5)
    for x, y in zip(jax.tree_util.tree_leaves(cache_a),
                    jax.tree_util.tree_leaves(cache_v)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   atol=2e-5, rtol=2e-5)


def test_batch_generate_shapes_and_determinism():
    prompts = np.arange(8, dtype=np.int32).reshape(2, 4) + 1
    a = batch_generate(_engine(slots=2, max_len=16, seed=3), prompts, 6)
    b = batch_generate(_engine(slots=2, max_len=16, seed=3), prompts, 6)
    assert a.shape == (2, 6)
    np.testing.assert_array_equal(a, b)


# -- traffic determinism (satellite f) ----------------------------------------


def test_traffic_schedule_is_pinned():
    cfg = TrafficConfig(rate=4.0, n_requests=5, vocab_size=512, seed=0)
    reqs = generate_requests(cfg)
    # literal schedule for seed 0 — a change here means BENCH_serve rows
    # stopped being comparable across commits
    np.testing.assert_allclose(
        [r.arrival for r in reqs],
        [0.169983, 0.424882, 0.429834, 0.430401, 0.567987], atol=1e-6)
    assert [len(r.prompt) for r in reqs] == [16, 32, 16, 16, 32]
    assert [r.max_new_tokens for r in reqs] == [32, 16, 16, 16, 32]
    assert reqs[0].prompt[:6].tolist() == [142, 417, 343, 1, 201, 438]


def test_traffic_same_seed_identical_different_seed_not():
    cfg = TrafficConfig(rate=8.0, n_requests=12, vocab_size=128, seed=7)
    a, b = generate_requests(cfg), generate_requests(cfg)
    for ra, rb in zip(a, b):
        assert ra.arrival == rb.arrival
        assert ra.max_new_tokens == rb.max_new_tokens
        np.testing.assert_array_equal(ra.prompt, rb.prompt)
    c = generate_requests(TrafficConfig(rate=8.0, n_requests=12,
                                        vocab_size=128, seed=8))
    assert [r.arrival for r in a] != [r.arrival for r in c]


# -- federation integration ---------------------------------------------------


def test_federation_round_publishes_feed_the_watcher(tmp_path):
    from repro.serve.federation import token_silos, train_and_publish

    # shrink widths only: the smoke stack fixes the layer count
    cfg = get_smoke_config("smollm-360m").replace(
        d_model=32, n_heads=2, n_kv_heads=1, head_dim=16, d_ff=64,
        vocab_size=64,
    )
    silos = token_silos(cfg, hospitals=2, n_per=12, seq_len=8, seed=0)
    report, pub = train_and_publish(
        "fl", cfg, str(tmp_path), rounds=3, batch_size=8, seed=0,
        silos=silos,
    )
    assert report.rounds_completed == 3
    assert pub.published == [0, 1, 2]
    assert list_rounds(str(tmp_path)) == [0, 1, 2]

    engine = ServeEngine(ServeConfig(arch="smollm-360m", slots=1,
                                     max_len=16), model_cfg=cfg)
    watcher = CheckpointWatcher(str(tmp_path))
    assert engine.poll_watcher(watcher)
    assert engine.serving_round == 2      # newest round wins
    # trained params serve: a generation completes under them
    r = Request(rid=0, arrival=0.0,
                prompt=np.arange(1, 5, dtype=np.int32), max_new_tokens=4)
    engine.admit(r)
    while engine.busy():
        engine.step()
    assert len(r.tokens) == 4
