"""``ops.aes_ctr``, the AES call path of a function invocation: host bytes
go to the device in one buffer with their counter, device bytes and the
counter apart; both give the reference's bytes."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


# 38 x 40800 is the first counter past the benchmark's 51 s window at
# 800/s; 2100 blocks take three grid steps and cross a counter byte.
# Interpret mode compiles once per shape and path, so one 2100-block case.
@pytest.mark.parametrize("backend", ["pallas_interpret", "xla"])
@pytest.mark.parametrize("n_blocks,nonce", [(1, 0), (1, 38 * 40800), (38, 0), (38, 65000),
                                            (38, 38 * 40800), (2100, 65000)])
def test_aes_ctr_host_and_device_inputs(n_blocks, nonce, backend):
    key_bytes = jax.device_put(jnp.arange(16, dtype=jnp.int32))
    pt = np.random.default_rng(n_blocks).integers(0, 256, (n_blocks, 16)).astype(np.int32)
    want = np.asarray(ref.aes_ctr_ref(jnp.asarray(pt), key_bytes, nonce))
    from_host = ops.aes_ctr(pt, key_bytes, nonce=nonce, backend=backend)
    from_device = ops.aes_ctr(jax.device_put(pt), key_bytes, nonce=nonce, backend=backend)
    for ct in (from_host, from_device):
        assert ct.shape == (n_blocks, 16) and ct.dtype == jnp.int32
        np.testing.assert_array_equal(ct, want)


def test_aes_ctr_host_program_takes_one_buffer_and_the_key():
    pt = np.zeros((38, 16), np.int32)
    key_bytes = jax.device_put(jnp.arange(16, dtype=jnp.int32))
    args = ops.aes_ctr_args(pt, key_bytes, 38 * 40800)
    assert args[0].shape == (39, 16) and args[0].dtype == np.int32
    assert args[0][38, 0] == 38 * 40800 and not args[0][38, 1:].any()
    lowered = ops.aes_ctr_program.lower(*args, backend="pallas_interpret")
    params = jax.tree.leaves(lowered.args_info)
    assert [(p.shape, p.dtype) for p in params] == [((39, 16), jnp.int32), ((16,), jnp.int32)]
    main = lowered.compiler_ir("stablehlo").body.operations[0]
    assert main.name.value == "main" and len(main.arguments) == 2


@pytest.mark.parametrize("on_device", [False, True])
@pytest.mark.parametrize("nonce", [2**31, -2**31 - 1])
def test_aes_ctr_counter_outside_int32_raises(nonce, on_device):
    pt = np.zeros((2, 16), np.int32)
    with pytest.raises(OverflowError):
        ops.aes_ctr(jax.device_put(pt) if on_device else pt, jnp.arange(16, dtype=jnp.int32),
                    nonce=nonce, backend="xla")


@pytest.mark.parametrize("on_device", [False, True])
def test_aes_ctr_lowers_a_new_signature_off_the_callers_stack(on_device):
    """The first call for a shape traces and lowers in a thread of its own;
    the caller's own call then finds the program compiled."""
    lowered_in = []

    def on_duration(event, duration_secs, **kwargs):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered_in.append(threading.get_ident())

    pt = np.zeros((7 if on_device else 5, 16), np.int32)
    key_bytes = jax.device_put(jnp.arange(16, dtype=jnp.int32))
    data = jax.device_put(pt) if on_device else pt
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        ct = ops.aes_ctr(data, key_bytes, nonce=3, backend="xla")
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert lowered_in and threading.get_ident() not in lowered_in
    np.testing.assert_array_equal(ct, ref.aes_ctr_ref(jnp.asarray(pt), key_bytes, 3))
