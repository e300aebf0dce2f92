"""The serving engine's decode step (``ServingEngine.decode_s``, host clock
to ``block_until_ready``): total over the window / steps, in ms."""


def read(obs):
    steps = getattr(obs, "decode_s", None)
    return 1e3 * sum(steps) / len(steps) if steps else None
