"""The serving engine's prefill step (``ServingEngine.prefill_s``, host
clock to ``block_until_ready``), median over the window, in ms."""
import statistics


def read(obs):
    steps = getattr(obs, "prefill_s", None)
    return 1e3 * statistics.median(steps) if steps else None
