"""Share of its roofline, in %, that the decode program reaches: the least
time the chip could take for the traced decode steps (the larger of their
bytes over peak bandwidth and their operations over peak bf16 rate,
counted from shapes in ``bench.flops``) over the device time of the
program's runs (``jit__decode``, ``repro.serving.engine``) in the trace."""
from bench import flops
from bench import trace as tr

PROGRAM = "jit__decode"


def read(obs):
    trace = getattr(obs, "trace", None)
    if trace is None:
        return None
    runs = tr.module_runs(trace, PROGRAM)
    steps = [b for b in obs.batches if b.traced]
    kv = [[b.prompt_len + k + 1] * b.clients for b in steps for k in range(b.decode_steps)]
    if not runs or len(runs) != len(kv):
        return None
    least = sum(flops.roofline_seconds(flops.decode_flops(obs.config, lens),
                                       flops.decode_bytes(obs.config, lens), obs.peaks)[0]
                for lens in kv)
    device_s = sum(e - s for s, e in runs) / 1e9
    return 100.0 * least / device_s
