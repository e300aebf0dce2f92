"""Host dispatch of one AES invocation: the benchmark's span around each
``ops.aes_ctr`` call, from call to return (the enqueue), median, in us."""
import statistics


def read(obs):
    spans = getattr(obs, "dispatch_s", None)
    return 1e6 * statistics.median(spans) if spans else None
