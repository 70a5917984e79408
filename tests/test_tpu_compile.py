"""The Pallas kernels of the main path compile for a TPU v5e chip.

Each test lowers a kernel at real widths against a described (not attached)
``v5e:2x2`` topology and asserts the compiled program contains the kernel
(``tpu_custom_call``).  Nothing runs: these catch what the chip's compiler
refuses (block tiling, VMEM, scalar stores) and interpret mode accepts.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU compiler library, and a test
worker that skips here must collect the same tests as every other worker.
The persistent compilation cache is off around these compiles — an entry
compiled for a described chip cannot be read back without one.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.ghost_norm.kernel import ghost_norm_pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel_compiles(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ghost_norm_compiles_at_smollm_widths(one_chip):
    # smollm-360m's MLP up-projection: d_model 960 -> d_ff 2560, seq 256
    a = _sds(one_chip, (8, 256, 960), jnp.bfloat16)
    g = _sds(one_chip, (8, 256, 2560), jnp.bfloat16)
    _assert_kernel_compiles(ghost_norm_pallas, a, g)


@pytest.mark.parametrize("d_in,d_out", [(256, 256), (256, 128), (256, 512),
                                        (512, 256), (256, 1024)])
def test_ghost_norm_compiles_at_lm_full_under_silo_vmap(one_chip, d_in, d_out):
    # the "lm"/full preset's dense layers (seq 64, batch 16), vmapped over
    # 4 silos as in the fused cohort round step
    a = _sds(one_chip, (4, 16, 64, d_in), jnp.float32)
    g = _sds(one_chip, (4, 16, 64, d_out), jnp.float32)
    _assert_kernel_compiles(jax.vmap(ghost_norm_pallas), a, g)


@pytest.mark.parametrize("cache_len", [512, 2048])
def test_decode_attention_compiles_at_smollm_serving_shapes(one_chip,
                                                           cache_len):
    # one request per slot (batch 1), vmapped over 4 slots as in
    # tf.decode_step_positions; h=15, kv=5, head_dim=64
    q = _sds(one_chip, (4, 1, 1, 15, 64), jnp.bfloat16)
    k = _sds(one_chip, (4, 1, cache_len, 5, 64), jnp.bfloat16)
    index = _sds(one_chip, (4,), jnp.int32)
    _assert_kernel_compiles(jax.vmap(decode_attention_pallas), q, k, k, index)


def test_flash_attention_compiles_at_smollm_widths(one_chip):
    q = _sds(one_chip, (1, 2048, 15, 64), jnp.bfloat16)
    k = _sds(one_chip, (1, 2048, 5, 64), jnp.bfloat16)
    _assert_kernel_compiles(flash_attention_pallas, q, k, k)
