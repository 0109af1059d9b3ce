"""The device trace: capture a slice of the window, reduce it to numbers.

``Tracer`` runs JAX's profiler over a few seconds in the middle of the
window and drops a clock marker (a ``TraceAnnotation``) whose host-clock
time it notes, so the trace's device events and the benchmark's own
request records share one clock.  ``reduce_trace`` turns the device
planes into busy time (the union of op intervals), time per op name, and
idle gaps, each gap named by the request kind in flight during it.

``PEAKS`` holds the published peaks per ``device_kind``; an unknown kind
is an error, never a default.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
#: 16 GB HBM at 819 GB/s.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}

MARKER = "perfbench_clock"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
DEVICE_LINE_PREFERENCE = ("XLA Ops", "XLA Modules")
_HLO = re.compile(r"^(%[\w.\-]+) = .*?\b([a-z][a-z0-9\-_]*)\(")
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def op_name(event_name: str) -> str:
    """``%sort.2 sort f32[32,262144]`` from an HLO instruction's text:
    its name, its opcode and its first shape."""
    m = _HLO.match(event_name)
    if m is None:
        return event_name[:80]
    shape = _SHAPE.search(event_name)
    return f"{m.group(1)} {m.group(2)} {shape.group(0) if shape else ''}".strip()


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None


@dataclasses.dataclass
class Trace:
    """A reduced trace, on the host clock (seconds, ``perf_counter``)."""

    window: Tuple[float, float]
    busy: List[Tuple[float, float]]      # merged device-busy intervals
    op_seconds: Dict[str, float]         # device op name -> seconds per device

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        return sum(b - a for a, b in self.busy)

    def busy_within(self, lo: float, hi: float) -> float:
        return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in self.busy)

    def gaps(self) -> List[Tuple[float, float]]:
        out, t = [], self.window[0]
        for a, b in self.busy:
            if a > t:
                out.append((t, a))
            t = max(t, b)
        if t < self.window[1]:
            out.append((t, self.window[1]))
        return out


def merge(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def reduce_events(device_events: Dict[str, List[Tuple[str, float, float]]],
                  window: Tuple[float, float]) -> Trace:
    """Per-device ``(name, start_s, end_s)`` events on the host clock ->
    :class:`Trace`: busy intervals of the busiest device, op seconds
    averaged over the devices that ran anything."""
    lo, hi = window
    busy_all: List[List[Tuple[float, float]]] = []
    ops: Dict[str, float] = {}
    for events in device_events.values():
        clipped = [(max(a, lo), min(b, hi), n) for n, a, b in events if b > lo and a < hi]
        for a, b, n in clipped:
            ops[n] = ops.get(n, 0.0) + (b - a)
        busy_all.append(merge([(a, b) for a, b, _ in clipped]))
    busy_all = [b for b in busy_all if b] or [[]]
    # the device with the most busy time stands for the cell's chips
    busy = max(busy_all, key=lambda b: sum(y - x for x, y in b))
    n_dev = max(1, len([b for b in busy_all if b]))
    return Trace(window=window, busy=busy, op_seconds={k: v / n_dev for k, v in ops.items()})


def read_profile(path: str, marker_pc_ns: int) -> Dict[str, List[Tuple[str, float, float]]]:
    """Device events of a ``.xplane.pb`` on the host clock: the marker's
    trace time is matched to the host time noted when it was emitted."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    marker_ns = None
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == MARKER and marker_ns is None:
                        marker_ns = e.start_ns
            continue
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        chosen = next((lines[n] for n in DEVICE_LINE_PREFERENCE if n in lines), None)
        if chosen is None:
            continue
        devices[plane.name] = [(op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                               for e in chosen.events]
    if marker_ns is None:
        raise RuntimeError(f"{path}: the clock marker {MARKER!r} is missing")
    shift = marker_pc_ns - marker_ns
    return {k: [(n, (a + shift) * 1e-9, (b + shift) * 1e-9) for n, a, b in v]
            for k, v in devices.items()}


def profile_layout(path: str) -> List[str]:
    """Plane and line names with event counts (for looking at a trace)."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"{plane.name}: " + ", ".join(
            f"{line.name}={sum(1 for _ in line.events)}" for line in plane.lines))
    return out


class Tracer:
    """Traces ``length`` seconds from ``offset`` seconds after :meth:`begin`,
    from its own thread."""

    def __init__(self, out_dir: str, offset: float, length: float):
        self.out_dir, self.offset, self.length = out_dir, offset, length
        self.start = 0.0
        self.window: Optional[Tuple[float, float]] = None
        self.marker_pc_ns = 0
        self.error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._run, name="perfbench-tracer")

    def _run(self) -> None:
        import jax

        try:
            time.sleep(max(0.0, self.start - time.perf_counter()))
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # no per-function Python events
            options.host_tracer_level = 1    # the clock marker, JAX dispatch
            jax.profiler.start_trace(self.out_dir, profiler_options=options)
            try:
                self.marker_pc_ns = time.perf_counter_ns()
                with jax.profiler.TraceAnnotation(MARKER):
                    pass
                t0 = self.marker_pc_ns * 1e-9
                time.sleep(max(0.0, t0 + self.length - time.perf_counter()))
                self.window = (t0, time.perf_counter())
            finally:
                jax.profiler.stop_trace()
        except BaseException as e:  # surfaced by join(), never swallowed
            self.error = e

    def begin(self) -> "Tracer":
        self.start = time.perf_counter() + self.offset
        self._thread.start()
        return self

    def join(self) -> Trace:
        self._thread.join()
        if self.error is not None:
            raise self.error
        files = sorted(glob.glob(os.path.join(self.out_dir, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not files:
            raise RuntimeError(f"no trace written under {self.out_dir}")
        self.path = files[-1]
        return reduce_events(read_profile(self.path, self.marker_pc_ns), self.window)


def name_gaps(trace: Trace, records: Sequence[dict], top: int = 10) -> List[List[object]]:
    """The longest device-idle gaps, each named by the request kind in
    flight over most of it (``idle`` where no request was)."""
    spans = [(r["start"], r["end"], r["spec"]["kind"]) for r in records
             if r["start"] is not None and r["end"] is not None]
    out = []
    for a, b in sorted(trace.gaps(), key=lambda g: g[0] - g[1])[:top]:
        cover: Dict[str, float] = {}
        for s, e, kind in spans:
            o = min(b, e) - max(a, s)
            if o > 0:
                cover[kind] = cover.get(kind, 0.0) + o
        name = max(cover, key=cover.get) if cover else "idle"
        out.append([name, b - a])
    return out


def top_ops(trace: Trace, top: int = 10) -> List[List[object]]:
    return [[n, s] for n, s in sorted(trace.op_seconds.items(), key=lambda kv: -kv[1])[:top]]
