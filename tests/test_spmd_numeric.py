"""Numeric SPMD equivalence (subprocess: needs 8 placeholder devices).

Proves the sharded programs compute the SAME VALUES as single-device
execution — the dry-run proves lowering; this proves semantics:

  * DeCaPH train step (per-example clip + noise) on a (4,2) mesh == the
    same step on 1 device (same rng),
  * ghost train step mesh == single-device,
  * decode with the KV-cache *sequence* sharded over data (the long_500k
    layout) == unsharded decode.
"""

import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_smoke_config
from repro.core import dp as dp_lib
from repro.core.ghost import ghost_clipped_grad_sum
from repro.launch import sharding as sh
from repro.launch.mesh import make_debug_mesh
from repro.models import transformer as tf
from repro.models.layers import activation_sharding

mesh = make_debug_mesh(4, 2)
policy = sh.ShardingPolicy()
results = {}

# ---- DeCaPH train-step gradient: mesh vs single device -------------------
cfg = get_smoke_config("smollm-360m").replace(d_ff=256)
params = tf.init(cfg, jax.random.key(0))
B, S = 8, 16
batch = {
    "tokens": jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size),
    "labels": jax.random.randint(jax.random.key(2), (B, S), 0, cfg.vocab_size),
}

def clipped(p, b):
    g, loss = dp_lib.per_example_clipped_grad_sum(
        lambda pp, ex: tf.per_example_loss_fn(cfg, pp, ex), p, b,
        clip_norm=0.5, microbatch_size=4,
    )
    return g, loss

g_single, loss_single = jax.jit(clipped)(params, batch)

pspecs = sh.param_specs(params, mesh, policy)
bspecs = sh.batch_specs(batch, mesh, policy)
params_sh = jax.device_put(params, pspecs)
batch_sh = jax.device_put(batch, bspecs)
rules = sh.activation_rules(mesh, policy, global_batch=B)

def clipped_mesh(p, b):
    with activation_sharding(rules):
        return clipped(p, b)

g_mesh, loss_mesh = jax.jit(clipped_mesh)(params_sh, batch_sh)
err = max(
    float(jnp.max(jnp.abs(a - b)))
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(g_mesh)),
                    jax.tree_util.tree_leaves(jax.device_get(g_single)))
)
results["train_grad_err"] = err
results["train_loss_err"] = abs(float(loss_mesh) - float(loss_single))

# ---- ghost step: mesh vs single ------------------------------------------
cfg_g = get_smoke_config("olmo-1b").replace(tie_embeddings=False)
params_g = tf.init(cfg_g, jax.random.key(3))
gg_single, _, norms_single = jax.jit(
    lambda p, b: ghost_clipped_grad_sum(cfg_g, p, b, clip_norm=0.5)
)(params_g, batch)
pspecs_g = sh.param_specs(params_g, mesh, policy)
params_g_sh = jax.device_put(params_g, pspecs_g)

def ghost_mesh(p, b):
    with activation_sharding(rules):
        return ghost_clipped_grad_sum(cfg_g, p, b, clip_norm=0.5)

gg_mesh, _, norms_mesh = jax.jit(ghost_mesh)(params_g_sh, batch_sh)
results["ghost_norm_err"] = float(jnp.max(jnp.abs(norms_mesh - norms_single)))
results["ghost_grad_err"] = max(
    float(jnp.max(jnp.abs(a - b)))
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(gg_mesh)),
                    jax.tree_util.tree_leaves(jax.device_get(gg_single)))
)

# ---- decode with sequence-sharded KV cache (long_500k layout) -------------
cfg_d = get_smoke_config("gemma-7b")
params_d = tf.init(cfg_d, jax.random.key(4))
b1 = 1
toks = jax.random.randint(jax.random.key(5), (b1, 12), 0, cfg_d.vocab_size)
cache = tf.init_cache(cfg_d, b1, 16)
logits_ref = None
for t in range(6):
    logits_ref, cache = tf.decode_step(cfg_d, params_d, cache,
                                       toks[:, t:t+1], jnp.asarray(t, jnp.int32))

cache_sh = tf.init_cache(cfg_d, b1, 16)
cspec = sh.cache_specs(jax.eval_shape(lambda: cache_sh), mesh, policy,
                       global_batch=b1)
cache_sh = jax.device_put(cache_sh, cspec)
params_d_sh = jax.device_put(params_d, sh.param_specs(params_d, mesh, policy))
rules_d = sh.activation_rules(mesh, policy, global_batch=b1, shard_kv_seq=True)

@jax.jit
def dstep(p, c, tok, i):
    with activation_sharding(rules_d):
        return tf.decode_step(cfg_d, p, c, tok, i)

logits_sh = None
for t in range(6):
    logits_sh, cache_sh = dstep(params_d_sh, cache_sh, toks[:, t:t+1],
                                jnp.asarray(t, jnp.int32))
results["decode_err"] = float(jnp.max(jnp.abs(
    jax.device_get(logits_sh) - jax.device_get(logits_ref)
)))
print("RESULT::" + json.dumps(results))
"""


@pytest.mark.slow
def test_spmd_numeric_equivalence():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", SCRIPT],
                         capture_output=True, text=True, timeout=560, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT::")][0]
    res = json.loads(line[len("RESULT::"):])
    assert res["train_grad_err"] < 2e-4, res
    assert res["train_loss_err"] < 1e-4, res
    assert res["ghost_norm_err"] < 2e-4, res
    assert res["ghost_grad_err"] < 2e-4, res
    assert res["decode_err"] < 2e-3, res
