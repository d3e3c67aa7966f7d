"""Process set-up shared by every cell: the compile cache, the chip check,
compile accounting and the device record."""

from __future__ import annotations

import sys
import threading

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """The accelerator the cell needs is not there."""


def use_compile_cache() -> str:
    """Keep JAX's persistent compilation cache in the program's own fixed
    directory inside the checkout (``compile_cache.REPO_CACHE``), also
    where ``JAX_COMPILATION_CACHE_DIR`` names another: a run reads and
    writes nothing outside its checkout, so that two checkouts under
    comparison share no cache.  Every program is cached, however small or
    quick to compile, so that a later run compiles nothing.  Returns the
    directory."""
    import jax

    from repro.launch.compile_cache import REPO_CACHE

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return str(REPO_CACHE)


def require_chips(n: int):
    """The devices to run on; raises ``NoChip`` without an accelerator or
    with fewer than ``n`` of them."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoChip(f"JAX found no accelerator (backend "
                     f"{jax.default_backend()!r})")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devices)}")
    return devices


class CompileCounter:
    """Counts backend compilations while it is open."""

    def __init__(self):
        import jax

        self.count = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, secs, **_):
        if event == COMPILE_EVENT:
            with self._lock:
                self.count += 1

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_event)


def device_record(devices) -> dict:
    """Platform, kind, count and the peak bytes of the fullest device."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def log(*parts) -> None:
    print("[bench]", *parts, file=sys.stderr, flush=True)
