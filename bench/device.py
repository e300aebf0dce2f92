"""The chips a run uses: the refusal of anything but a TPU, the compile
cache, peak memory and the profiler's options."""
from __future__ import annotations

import os
from pathlib import Path


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int) -> dict:
    """The device as JAX reports it; raises :class:`NoAccelerator` otherwise."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoAccelerator(f"JAX found no device: {e}") from None
    d = devs[0]
    if d.platform != "tpu":
        raise NoAccelerator(f"the benchmark needs a TPU; JAX found platform "
                            f"{d.platform!r} ({d.device_kind})")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where it is set,
    else ``.jax_cache/`` in the checkout, a fixed path (it is part of the key).
    Every program is cached, however fast it compiles."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(Path(root) / ".jax_cache"))
    if not os.environ.get("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


def memory_peak_bytes() -> int:
    """``peak_bytes_in_use`` of the fullest local device."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def profile_options():
    """Host spans and the device trace, without the Python function tracer."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


class CompileCounter:
    """Counts the programs JAX hands to its compiler or its persistent cache
    while open: a window that compiles counts more than none."""
    EVENT = "/jax/compilation_cache/compile_requests_use_cache"

    def __init__(self):
        self.count = 0

    def _on(self, event: str, **kwargs) -> None:
        if event == self.EVENT:
            self.count += 1

    def __enter__(self) -> "CompileCounter":
        import jax
        jax.monitoring.register_event_listener(self._on)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        jax.monitoring.unregister_event_listener(self._on)
