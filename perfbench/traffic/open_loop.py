"""Open loop: requests sent on a schedule, whether or not earlier ones came back.

Parameters: ``rate`` (requests/s).  A window of ``seconds`` holds
round(rate * seconds) arrivals with the same exponential gaps for every
seed (one fixed set, scaled to the window and permuted by the seed), and
the mix's entries in exact proportions (largest remainder) in a seeded
order.  Latency runs from each request's due time (``perfbench/lib/drive.py``).
"""

from __future__ import annotations

from typing import Callable, List, Optional, Tuple

import numpy as np

from perfbench.lib import drive
from perfbench.lib.traffic import exact_counts

_GAP_STREAM = 0x5EED  # the seed-independent stream of open-loop gaps


def schedule(traffic: dict, seed: int, seconds: float, requests) -> Tuple[np.ndarray, List[dict]]:
    """Due times (s from the window's start) and the requests."""
    n = max(1, int(round(traffic["rate"] * seconds)))
    gaps = np.random.default_rng([_GAP_STREAM, n]).exponential(1.0, size=n)
    rng = np.random.default_rng([seed, 1])
    gaps = rng.permutation(gaps) * (seconds / gaps.sum())
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    mix = traffic["mix"]
    kinds = np.repeat(np.arange(len(mix)), exact_counts([e["weight"] for e in mix], n))
    kinds = rng.permutation(kinds)
    return due, [requests.make(rng, mix[int(j)], traffic) for j in kinds]


def window_requests(traffic: dict, seed: int, seconds: float, requests) -> List[dict]:
    return schedule(traffic, seed, seconds, requests)[1]


def drive_window(traffic: dict, seed: int, seconds: float, requests, system,
                 on_open: Optional[Callable[[], object]] = None) -> tuple:
    """Run the window; returns (records, t0, t_close)."""
    due, specs = schedule(traffic, seed, seconds, requests)
    if on_open:
        on_open()
    return drive.run_open(lambda spec: requests.submit(system, spec), due, specs, seconds)
