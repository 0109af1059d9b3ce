"""Shared arithmetic of the per-layer metric readers (``perfbench/metrics``).

Each reads the traced slice of the window: the device's busy time there,
and the benchmark's own request records on the same clock.
"""

from __future__ import annotations

from typing import List, Optional

from perfbench.lib import tracing


def finished_in_trace(run) -> List[dict]:
    """Requests answered inside the traced slice."""
    lo, hi = run.trace.window
    return [r for r in run.records
            if r["error"] is None and r["end"] is not None and lo <= r["end"] < hi]


def device_ms_per_request(run, surface: str) -> Optional[float]:
    if run.trace is None or run.surface != surface:
        return None
    done = finished_in_trace(run)
    if not done or run.trace.busy_s <= 0:
        return None
    return run.trace.busy_s / len(done) * 1e3


def host_ms_per_request(run, surface: str) -> Optional[float]:
    """Request span minus the device's busy time inside it, averaged over
    the requests whose whole span lies in the traced slice."""
    if run.trace is None or run.surface != surface:
        return None
    lo, hi = run.trace.window
    inside = [r for r in run.records if r["error"] is None and r["start"] is not None
              and r["end"] is not None and lo <= r["start"] and r["end"] < hi]
    if not inside:
        return None
    host = sum((r["end"] - r["start"]) - run.trace.busy_within(r["start"], r["end"])
               for r in inside)
    return host / len(inside) * 1e3


def scan_roofline(run, surface: str) -> Optional[float]:
    """One float32 pass over the corpus matrix per answered request, at the
    chip's HBM bandwidth, as a percentage of the device's busy time."""
    if run.trace is None or run.surface != surface or run.trace.busy_s <= 0:
        return None
    done = finished_in_trace(run)
    if not done:
        return None
    bw = tracing.peaks(str(run.device["kind"]))["hbm_bytes_per_s"]
    least = len(done) * int(run.cfg["rows"]) * int(run.cfg["dim"]) * 4 / bw
    return least / run.trace.busy_s * 100.0
