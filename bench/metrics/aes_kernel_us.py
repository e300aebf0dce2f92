"""Device time of the AES kernel per invocation, in us: the device
durations of the kernel's custom call (the ``aes_ctr`` ``pallas_call`` in
``repro.kernels.aes_ctr``) in the traced window, over the invocations
dispatched in it."""
from bench import trace as tr


def _is_kernel(hlo: str) -> bool:
    return tr.short_op(hlo).startswith("aes_ctr") and " custom-call(" in hlo


def read(obs):
    trace, n = getattr(obs, "trace", None), getattr(obs, "traced_invocations", 0)
    if trace is None or not n:
        return None
    seconds, count = tr.op_seconds(trace, _is_kernel)
    return 1e6 * seconds / n if count else None
