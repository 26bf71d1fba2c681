"""Device checks, the persistent compile cache, and compile counting."""
from __future__ import annotations

import contextlib
import os
from pathlib import Path

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_chips(chips: int) -> list:
    """The first ``chips`` devices; raises when they are not TPUs."""
    devs = jax.devices()
    kind = devs[0].device_kind
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"needs a TPU; JAX found {len(devs)} "
                            f"{devs[0].platform} device(s) ({kind})")
    if len(devs) < chips:
        raise NoAccelerator(f"needs {chips} chips; JAX found {len(devs)} "
                            f"{devs[0].platform} device(s) ({kind})")
    return devs[:chips]


def info(devs: list) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs: list) -> int | None:
    """Peak bytes in use on the fullest of ``devs`` (None where the
    backend keeps no count, as the CPU)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compile cache at ``<checkout>/.jax_cache`` (a fixed
    path: the path is part of an entry's key), or where
    ``JAX_COMPILATION_CACHE_DIR`` says. Every program is cached, however
    short its compile, so a warm run compiles nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@contextlib.contextmanager
def log_compiles():
    """Log the name of anything compiled in the measured window (there
    should be nothing), so that a stall there can be traced to its cause."""
    was = jax.config.jax_log_compiles
    jax.config.update("jax_log_compiles", True)
    try:
        yield
    finally:
        jax.config.update("jax_log_compiles", was)


class CompileLog:
    """Counts backend compiles (a persistent-cache hit is not one)."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += secs
