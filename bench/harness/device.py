"""The chip: refuse to run without one, describe it, and count compiles."""
from __future__ import annotations

import os
import threading


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def configure_compile_cache(root) -> str:
    """JAX's persistent compile cache at a fixed path in the checkout
    (or where JAX_COMPILATION_CACHE_DIR says), keeping every compile,
    however short.  Call before the first compile."""
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def require_chips(n: int) -> list:
    """The first `n` TPU devices; raises NoChip otherwise."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:              # no backend could start
        raise NoChip(f"JAX found no device: {e}") from None
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX platform is {devices[0].platform!r}, not tpu; "
                     "the benchmark measures the chip only")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found "
                     f"{len(devices)}")
    return devices[:n]


def record(devices) -> dict:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class CompileClock:
    """Backend compiles (count and seconds) and persistent-cache hits,
    from JAX's monitoring events.  `take()` returns and zeroes them."""

    def __init__(self):
        import jax.monitoring as mon

        self._lock = threading.Lock()
        self._zero()

        def on_duration(event, duration, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                with self._lock:
                    self.compiles += 1
                    self.seconds += duration

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                with self._lock:
                    self.cache_hits += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def _zero(self):
        self.compiles, self.seconds, self.cache_hits = 0, 0.0, 0

    def take(self) -> dict:
        with self._lock:
            out = {"compiles": self.compiles, "compile_s": self.seconds,
                   "cache_hits": self.cache_hits}
            self._zero()
        return out
