"""Driving the window: an open loop on a schedule, or closed-loop clients.

Every request gets a record with host-clock times (``time.perf_counter``):
``due`` (open loop: when the schedule says to send), ``start`` (when it was
sent), ``end`` (when its answer came back), its ``answer`` or ``error``.
An open-loop latency runs from ``due``, so a stall also charges the
requests that were due during it; ``start - due`` is how late the
generator ran.  A closed-loop latency runs from ``start``.
"""

from __future__ import annotations

import asyncio
import threading
import time
from typing import Awaitable, Callable, Dict, Iterator, List, Optional, Sequence

import numpy as np

LEAD_S = 0.005       # the first arrival is due this long after the window opens
DRAIN_S = 60.0       # answers are awaited this long past the window's close


def _record(spec: dict, client: int = 0) -> Dict[str, object]:
    return {"spec": spec, "client": client, "due": None, "start": None,
            "end": None, "answer": None, "error": None}


def run_open(submit: Callable[[dict], Awaitable[list]], due: np.ndarray,
             specs: Sequence[dict], seconds: float) -> tuple:
    """Send ``specs[i]`` at ``t0 + due[i]`` whether or not earlier answers
    came back; returns (records, t0, t_close)."""
    records = [_record(s) for s in specs]

    async def one(rec: dict) -> None:
        try:
            rec["answer"] = await submit(rec["spec"])
        except Exception as e:  # a failed request is counted, not raised
            rec["error"] = f"{type(e).__name__}: {e}"
        rec["end"] = time.perf_counter()

    async def main() -> tuple:
        t0 = time.perf_counter() + LEAD_S
        tasks = []
        for rec, d in zip(records, due):
            rec["due"] = t0 + float(d)
            delay = rec["due"] - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            rec["start"] = time.perf_counter()
            tasks.append(asyncio.ensure_future(one(rec)))
        close = t0 + seconds
        if tasks:
            await asyncio.wait(tasks, timeout=max(0.0, close + DRAIN_S - time.perf_counter()))
        for t in tasks:
            if not t.done():
                t.cancel()
        return t0, close

    t0, close = asyncio.run(main())
    return records, t0, close


def run_closed(call: Callable[[dict], list], streams: Sequence[Iterator[dict]],
               seconds: float) -> tuple:
    """One thread per client, each sending its next request when the last
    one is answered, until the window closes; returns (records, t0, t_close)."""
    records: List[List[dict]] = [[] for _ in streams]
    t0 = time.perf_counter() + LEAD_S
    close = t0 + seconds
    go = threading.Event()

    def client(c: int) -> None:
        go.wait()
        while True:
            now = time.perf_counter()
            if now >= close:
                return
            rec = _record(next(streams[c]), c)
            rec["due"] = rec["start"] = time.perf_counter()
            try:
                rec["answer"] = call(rec["spec"])
            except Exception as e:  # a failed request is counted, not raised
                rec["error"] = f"{type(e).__name__}: {e}"
            rec["end"] = time.perf_counter()
            records[c].append(rec)

    threads = [threading.Thread(target=client, args=(c,), name=f"perfbench-client-{c}")
               for c in range(len(streams))]
    for t in threads:
        t.start()
    while time.perf_counter() < t0:
        pass
    go.set()
    for t in threads:
        t.join(timeout=seconds + DRAIN_S)
    if any(t.is_alive() for t in threads):
        raise RuntimeError("a client did not finish within the drain time")
    return [r for rs in records for r in rs], t0, close


def summarize(records: Sequence[dict], t0: float, close: float) -> Dict[str, float]:
    """Latency percentiles over every request sent in the window, the rate
    completed within it, and how late the generator ran."""
    done = [r for r in records if r["error"] is None and r["end"] is not None]
    lat = np.asarray([(r["end"] - r["due"]) * 1e3 for r in done])
    late = np.asarray([(r["start"] - r["due"]) * 1e3 for r in records if r["start"] is not None])
    completed = sum(1 for r in done if r["end"] <= close)
    return {
        "attempted": len(records),
        "failed": len(records) - len(done),
        "completed_in_window": completed,
        "rate": completed / (close - t0),
        "p50_ms": float(np.percentile(lat, 50)) if lat.size else float("nan"),
        "p95_ms": float(np.percentile(lat, 95)) if lat.size else float("nan"),
        "p99_ms": float(np.percentile(lat, 99)) if lat.size else float("nan"),
        "max_ms": float(lat.max()) if lat.size else float("nan"),
        "late_p50_ms": float(np.percentile(late, 50)) if late.size else 0.0,
        "late_p95_ms": float(np.percentile(late, 95)) if late.size else 0.0,
        "late_max_ms": float(late.max()) if late.size else 0.0,
        "after_close": sum(1 for r in done if r["end"] > close),
    }


def by_kind(records: Sequence[dict]) -> Dict[str, Dict[str, float]]:
    """Count and p50/p95 latency of each mix entry (earlier-line detail)."""
    out: Dict[str, Dict[str, float]] = {}
    kinds: Dict[str, List[float]] = {}
    for r in records:
        if r["error"] is None and r["end"] is not None:
            kinds.setdefault(r["spec"]["kind"], []).append((r["end"] - r["due"]) * 1e3)
    for k, v in sorted(kinds.items()):
        a = np.asarray(v)
        out[k] = {"n": int(a.size), "p50_ms": round(float(np.percentile(a, 50)), 3),
                  "p95_ms": round(float(np.percentile(a, 95)), 3)}
    return out


def sample(records: Sequence[dict], n: int, seed: int,
           key: Optional[Callable[[dict], str]] = None) -> List[dict]:
    """Up to ``n`` records drawn from the seed, as evenly over ``key`` (the
    mix entry) as the window allows."""
    rng = np.random.default_rng([seed, 2])
    key = key or (lambda r: r["spec"]["kind"])
    groups: Dict[str, List[dict]] = {}
    for r in records:
        groups.setdefault(key(r), []).append(r)
    names = sorted(groups)
    out: List[dict] = []
    share = max(1, n // max(1, len(names)))
    for name in names:
        g = groups[name]
        take = rng.choice(len(g), size=min(share, len(g)), replace=False)
        out += [g[int(i)] for i in sorted(take)]
    return out
