"""Per-layer numbers from the program's own spans (``repro.core.spans``).

A traced run turns the program's span recorder on for the window and
hands its spans to the readers as ``run.spans``, and the device time per
``jax.named_scope`` stage in the traced slice as ``run.scope_seconds``
(``perfbench/run.py``); an untraced run has neither, and every reader here
returns None on it, as on a program that records no spans.

Spans are on ``time.perf_counter_ns``, the clock of the request records
and of the trace's clock marker, so they meet the device's busy intervals
with no conversion but ns -> s.  Every number is a time or a count inside
the traced slice over the requests answered there, as the other readers
(``layers.py``) average.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from perfbench.lib import layers, tracing

Interval = Tuple[float, float]

#: the stage scopes the device paths name (``core/backends.py``)
SCOPES = ("score", "select", "mmr")
_SCOPE = re.compile(r"(?:^|/)(" + "|".join(SCOPES) + r")/")
#: an op whose metadata lost its scope path, by HLO category: XLA's TopK
#: decomposition drops ``lax.top_k``'s metadata and leaves a bare ``sort``,
#: the only sort these graphs run
CATEGORY_SCOPES = {"sort": "select"}
#: spans open from a request's arrival to its answer, around its layer spans
UMBRELLA = ("engine.request", "sql.statement")


def seconds(sp) -> Interval:
    return sp.start_ns * 1e-9, sp.end_ns * 1e-9


def clipped(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def intersect(xs: Sequence[Interval], ys: Sequence[Interval]) -> List[Interval]:
    """Intersection of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_by_span(trace: tracing.Trace, records: Sequence[dict], spans) -> Dict[str, float]:
    """Seconds of device-idle time in the traced slice during which each
    span name was open on any thread, and ``none``: idle time inside some
    request's record while no layer span was open (the :data:`UMBRELLA`
    spans, open over the whole request, put idle time down to no layer)."""
    idle = trace.gaps()
    by_name: Dict[str, List[Interval]] = {}
    for sp in spans:
        by_name.setdefault(sp.name, []).append(seconds(sp))
    out = {name: length(intersect(idle, tracing.merge(iv)))
           for name, iv in sorted(by_name.items())}
    in_request = intersect(idle, tracing.merge(
        [(r["start"], r["end"]) for r in records
         if r["start"] is not None and r["end"] is not None]))
    covered = tracing.merge([iv for name, ivs in by_name.items()
                             if name not in UMBRELLA for iv in ivs])
    out["none"] = length(in_request) - length(intersect(in_request, covered))
    return out


def _ready(run, surface: str) -> Optional[List[dict]]:
    """The requests answered in the slice, or None where nothing can be
    read (no trace, no spans, another surface, nothing answered)."""
    if run.trace is None or run.spans is None or run.surface != surface:
        return None
    return layers.finished_in_trace(run) or None


def span_ms_per_request(run, surface: str, names: Sequence[str]) -> Optional[float]:
    """Milliseconds inside the slice spent in spans of ``names``, per
    request answered there."""
    done = _ready(run, surface)
    if done is None:
        return None
    lo, hi = run.trace.window
    total = length(clipped((seconds(sp) for sp in run.spans if sp.name in names), lo, hi))
    return total / len(done) * 1e3


def dispatch_ms_per_request(run, surface: str) -> Optional[float]:
    """``engine.device`` minus the device's busy time inside it: the host
    side of the device stage (fold, uploads, launch, sync)."""
    done = _ready(run, surface)
    if done is None:
        return None
    lo, hi = run.trace.window
    host = 0.0
    for a, b in clipped((seconds(sp) for sp in run.spans if sp.name == "engine.device"), lo, hi):
        host += (b - a) - run.trace.busy_within(a, b)
    return host / len(done) * 1e3


def upload_bytes_per_request(run, surface: str) -> Optional[float]:
    """Host-to-device bytes of the device passes that ended in the slice,
    per request answered there."""
    done = _ready(run, surface)
    if done is None:
        return None
    lo, hi = run.trace.window
    total = sum(sp.attrs.get("upload_bytes", 0) for sp in run.spans
                if sp.name == "engine.device" and lo <= sp.end_ns * 1e-9 < hi)
    return total / len(done)


def statement_queue_ms(run) -> Optional[float]:
    """``engine.queue`` of the engine requests a ``sql.statement`` owns,
    per statement answered in the slice."""
    done = _ready(run, "sql")
    if done is None:
        return None
    by_id = {sp.span_id: sp for sp in run.spans}

    def owned(sp) -> bool:
        req = by_id.get(sp.parent_id)
        return req is not None and by_id.get(req.parent_id, req).name == "sql.statement"

    lo, hi = run.trace.window
    total = length(clipped((seconds(sp) for sp in run.spans
                            if sp.name == "engine.queue" and owned(sp)), lo, hi))
    return total / len(done) * 1e3


def scope_ms_per_request(run, surface: str, scope: str) -> Optional[float]:
    """Device op time under one ``named_scope`` stage, per request."""
    done = _ready(run, surface)
    scopes = run.scope_seconds or {}
    if done is None or scope not in scopes:
        return None
    return scopes[scope] / len(done) * 1e3


def scope_of(texts: Iterable[object]) -> Optional[str]:
    """The stage scope an op ran under: the first text holding a scope
    path, ``.../score/...``, ``select/`` or ``mmr/`` (an op named
    ``select`` is no scope)."""
    for text in texts:
        m = _SCOPE.search(str(text)) if text else None
        if m:
            return m.group(1)
    return None


def _fields(buf: memoryview):
    """``(field number, value)`` of one protobuf message: an int for a
    varint or fixed field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            val, i = int.from_bytes(buf[i:i + width], "little"), i + width
        else:
            raise ValueError(f"protobuf wire type {wire} not expected in an XSpace")
        yield key >> 3, val


def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def op_scopes(path: str) -> Dict[str, Dict[str, Optional[str]]]:
    """Per device plane of a ``.xplane.pb``: op name (as
    ``tracing.op_name`` gives it) -> stage scope, read
    from the op's event metadata: a scope path in its string stats (a TPU
    op carries its ``named_scope`` path in ``tf_op``) or its names, else
    :data:`CATEGORY_SCOPES` of its ``hlo_category``.
    ``jax.profiler.ProfileData`` does not expose metadata stats, so the
    ``XSpace`` protobuf is read directly: ``XSpace.planes`` (1); ``XPlane``
    name (2), ``event_metadata`` (4) and ``stat_metadata`` (5) maps;
    ``XEventMetadata`` name (2), display name (4), stats (5); ``XStat``
    metadata id (1), str_value (5) or ref_value (7) to a stat metadata
    name."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, Optional[str]]] = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for k, v in _fields(plane):
            if k == 2:
                name = bytes(v).decode()
            elif k == 4:
                events.append(dict(_fields(v)).get(2, b""))
            elif k == 5:
                entry = dict(_fields(v))
                meta = dict(_fields(entry.get(2, b"")))
                stat_names[entry.get(1, 0)] = bytes(meta.get(2, b"")).decode(errors="replace")
        if not tracing.DEVICE_PLANE.match(name):
            continue
        ops: Dict[str, Optional[str]] = {}
        for ev in events:
            op, names, stats = "", [], {}
            for k, v in _fields(ev):
                if k in (2, 4):
                    names.append(bytes(v).decode(errors="replace"))
                    op = names[-1] if k == 2 else op
                elif k == 5:
                    stat = dict(_fields(v))
                    text = (bytes(stat[5]).decode(errors="replace") if 5 in stat
                            else stat_names.get(stat[7], "") if 7 in stat else "")
                    stats[stat_names.get(stat.get(1), "")] = text
            ops[tracing.op_name(op)] = (scope_of(list(stats.values()) + names)
                       or CATEGORY_SCOPES.get(stats.get("hlo_category", "")))
        out[name] = ops
    return out


def top_level(events: Sequence[Tuple[Optional[str], float, float]]) -> List[Tuple[Optional[str], float, float]]:
    """The ops not nested in another op of the same line (a ``while``
    holds its body's ops), so no time counts twice; a nesting op with no
    scope takes the scope of its first scoped child."""
    out: List[List] = []
    for s, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        if out and a < out[-1][2]:
            if out[-1][0] is None:
                out[-1][0] = s
            continue
        out.append([s, a, b])
    return [(s, a, b) for s, a, b in out]


def scope_seconds(events: Dict[str, List[Tuple[Optional[str], float, float]]],
                  window: Interval) -> Dict[str, float]:
    """Per-device ``(scope, start_s, end_s)`` op events -> seconds per
    scope inside ``window`` (``none`` for ops under no stage scope),
    averaged over the devices that ran anything, as ``reduce_events``
    averages op seconds."""
    lo, hi = window
    out: Dict[str, float] = {}
    ran = 0
    for evs in events.values():
        cut = [(s or "none", a, b) for s, a, b in evs if b > lo and a < hi]
        ran += bool(cut)
        for s, a, b in cut:
            out[s] = out.get(s, 0.0) + min(b, hi) - max(a, lo)
    return {k: v / max(ran, 1) for k, v in out.items()}


def read_scoped_profile(path: str, marker_pc_ns: int) -> Dict[str, List[Tuple[Optional[str], float, float]]]:
    """Device op events of a ``.xplane.pb`` as ``(scope, start_s, end_s)``
    on the host clock: ``tracing.read_profile``'s events, each op named by
    its stage scope (:func:`op_scopes`), the nested ones folded into their
    parent (:func:`top_level`)."""
    scopes = op_scopes(path)
    return {k: top_level([(scopes.get(k, {}).get(n), a, b) for n, a, b in v])
            for k, v in tracing.read_profile(path, marker_pc_ns).items()}
