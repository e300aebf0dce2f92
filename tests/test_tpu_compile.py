"""Compile rehearsal for one TPU v5e chip with no chip attached: the main
path's programs, at their real sizes, go through the TPU compiler, which
refuses what the chip would refuse (a lowering Mosaic lacks, too much
VMEM, a program that does not fit in HBM).  Nothing runs here, so these
tests say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.launch.serve import MAX_SEQ_LEN, engine_config
from repro.models import transformer as T

HBM_BYTES = 16e9      # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one, so keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree)


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < HBM_BYTES, total
    return total


@pytest.mark.parametrize("n_blocks", [38, 4096])    # 600 B; 64 KiB
def test_aes_kernel_compiles_for_v5e(one_chip, n_blocks):
    """The program an invocation from host bytes runs: the payload and its
    counter in one (N + 1, 16) buffer, and the key."""
    key = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)
    args = _on(one_chip, ops.aes_ctr_args(np.zeros((n_blocks, 16), np.int32), key, 38 * 40800))
    lowered = ops.aes_ctr_program.lower(*args, backend="pallas")
    params = [a.shape for a in jax.tree.leaves(lowered.args_info)]
    assert params == [(n_blocks + 1, 16), (16,)]
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_aes_kernel_device_input_compiles_for_v5e(one_chip):
    """The program for bytes already on the device: the counter is traced."""
    pt = jax.ShapeDtypeStruct((38, 16), jnp.int32, sharding=one_chip)
    key = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)
    nonce = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    lowered = ops.aes_ctr_program.lower(*ops.aes_ctr_args(pt, key, nonce), backend="pallas")
    assert len(jax.tree.leaves(lowered.args_info)) == 3
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


@pytest.fixture(scope="module")
def qwen3(one_chip):
    cfg = engine_config("qwen3-1.7b")
    params = _on(one_chip, jax.eval_shape(lambda k: T.init_params(cfg, k), jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((4, 32), jnp.int32, sharding=one_chip)
    return cfg, params, tokens


def test_qwen3_prefill_compiles_at_published_widths(one_chip, qwen3):
    cfg, params, tokens = qwen3
    prefill = jax.jit(lambda p, t: T.prefill(p, cfg, {"tokens": t}, seq_len=MAX_SEQ_LEN))
    _fits(prefill.lower(params, tokens).compile())


def test_qwen3_decode_step_compiles_at_published_widths(one_chip, qwen3):
    cfg, params, tokens = qwen3
    caches = _on(one_chip, jax.eval_shape(
        lambda p, t: T.prefill(p, cfg, {"tokens": t}, seq_len=MAX_SEQ_LEN)[1],
        params, tokens))
    decode = jax.jit(lambda p, t, pos, c: T.decode_step(p, cfg, t, pos, c))
    step = jax.ShapeDtypeStruct((4, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = decode.lower(params, step, pos, caches).compile()
    # bf16 weights of 1.7B parameters alone are 3.4 GB
    assert _fits(compiled) > 3.4e9
