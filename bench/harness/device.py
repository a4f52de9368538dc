"""The accelerator: bring JAX up on it, count compiles, read its memory."""
from __future__ import annotations


class NoAccelerator(RuntimeError):
    """JAX came up without the chips a cell asks for."""


def bring_up(chips: int, allow_cpu: bool):
    """Start JAX and return the devices.  ``repro.compile_cache`` keeps
    the persistent compile cache in ``.jax_cache/`` of the checkout unless
    ``JAX_COMPILATION_CACHE_DIR`` names another directory.  Raises
    :class:`NoAccelerator` unless JAX found at least ``chips`` TPUs;
    ``allow_cpu`` (CPU rehearsal) accepts the CPU instead."""
    import jax

    from repro import compile_cache
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not allow_cpu:
        raise NoAccelerator(f"JAX came up on {platform!r}, not on a TPU")
    if platform == "tpu" and allow_cpu:
        raise NoAccelerator("CPU rehearsal is for machines without a TPU")
    if platform == "tpu" and len(devices) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX found "
                            f"{len(devices)}")
    compile_cache.enable()
    return devices


class CompileStats:
    """Backend-compile seconds and count, and persistent-cache hits, from
    JAX's own monitoring events (a cache hit records its retrieval as the
    compile)."""

    def __init__(self) -> None:
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device (0 where JAX reports
    none, as on the CPU)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def describe(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
