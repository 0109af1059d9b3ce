"""Device identity, compile counting and device memory (JAX on the host).

``CompileClock`` and ``platform_or_exit`` are
copied from the program's ``chip_smoke.py``; ``CompileClock`` also counts
the traces and lowerings that JAX reports, which is how a compilation
inside the measured window shows.
"""

from __future__ import annotations

import contextlib
import sys
from typing import Dict, Iterator, Optional

import jax


class CompileClock:
    """XLA compile seconds and counts, summed from JAX's monitoring events
    while :meth:`watch` is open: ``traces`` (jaxpr traces), ``lowerings``
    (a new executable, compiled or read from the persistent cache),
    ``compiles`` (XLA backend compiles) and ``cache_hits``."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        self.seconds = 0.0
        self.traces = 0
        self.lowerings = 0
        self.compiles = 0
        self.cache_hits = 0

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == self.BACKEND_COMPILE:
            self.seconds += duration
            self.compiles += 1
        elif event == self.TRACE:
            self.traces += 1
        elif event == self.LOWER:
            self.lowerings += 1

    def _on_event(self, event: str, **_) -> None:
        if event == self.CACHE_HIT:
            self.cache_hits += 1

    def counts(self) -> Dict[str, float]:
        return {"traces": self.traces, "lowerings": self.lowerings,
                "compiles": self.compiles, "cache_hits": self.cache_hits,
                "compile_s": round(self.seconds, 3)}

    @contextlib.contextmanager
    def watch(self) -> Iterator["CompileClock"]:
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        try:
            yield self
        finally:
            jax.monitoring.unregister_event_duration_listener(self._on_duration)
            jax.monitoring.unregister_event_listener(self._on_event)


def memory_peak_bytes(chips: int) -> Optional[int]:
    """``peak_bytes_in_use`` on the fullest of the cell's chips."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def platform_or_exit(chips: int) -> Dict[str, object]:
    """The device as JAX reports it; exits non-zero, printing no result,
    unless JAX finds a TPU with at least ``chips`` chips."""
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"perfbench: JAX found platform {platform!r}, not a TPU; "
              "refusing to report a result", file=sys.stderr)
        raise SystemExit(3)
    if len(devices) < chips:
        print(f"perfbench: {len(devices)} TPU chips, {chips} needed", file=sys.stderr)
        raise SystemExit(3)
    return {"platform": platform, "kind": devices[0].device_kind, "count": chips}
