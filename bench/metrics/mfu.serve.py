"""The whole step's share of the chip's bf16 peak, in %: model operations
of every token the endpoint processed in the traced window (prefill and
decode, counted from shapes in ``bench.flops``) over the window times the
peak."""
from bench import flops


def read(obs):
    trace = getattr(obs, "trace", None)
    if trace is None:
        return None
    total = 0
    for b in (b for b in obs.batches if b.traced):
        total += flops.prefill_flops(obs.config, b.clients, b.prompt_len)
        total += sum(flops.decode_flops(obs.config, [b.prompt_len + k + 1] * b.clients)
                     for k in range(b.decode_steps))
    return 100.0 * total / (trace.window_s * obs.peaks["bf16_flops_per_s"])
