"""The chip: found or refused, named, and its memory read."""

import os


class NoChip(RuntimeError):
    pass


def require_tpu(chips):
    """The live devices. Raises NoChip on any backend but a TPU, or with
    fewer chips than the cell asks for: a measurement never falls back
    to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"the benchmark needs a TPU; JAX reports platform "
                     f"{devices[0].platform!r} ({devices[0].device_kind!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chip(s); JAX reports "
                     f"{len(devices)}")
    return devices[:chips]


def describe(devices):
    """The ``device`` object of the result line, as JAX reports it."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def enable_compile_cache(root):
    """JAX's persistent cache at a fixed path inside the checkout
    (``JAX_COMPILATION_CACHE_DIR`` wins where it is set; the program's
    own ``core/compile_cache.py`` follows the same rule). Every program
    is cached, however quick its compile, so that a second run compiles
    nothing."""
    import jax
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


class CompileCounter:
    """Counts backend compilations (jax.monitoring) so that a window can
    show it compiled nothing. Cache hits are counted apart."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration, **_):
        if event.endswith("backend_compile_duration"):
            self.compiles += 1

    def _on_event(self, event, **_):
        if event.endswith("/compilation_cache/cache_hits"):
            self.cache_hits += 1
