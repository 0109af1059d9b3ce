"""Closed loop: ``clients`` threads, each sending its next request when its
last one is answered.

Each client's stream is whole blocks of the mix's weights, each block in a
seeded order, so every seed sends the mix in its exact proportions.
Latency runs from when a request was sent (``perfbench/lib/drive.py``).
"""

from __future__ import annotations

import itertools
from typing import Callable, Iterator, List, Optional

import numpy as np

from perfbench.lib import drive


def stream(traffic: dict, seed: int, client: int, requests) -> Iterator[dict]:
    """One client's requests."""
    rng = np.random.default_rng([seed, 1, client])
    mix = traffic["mix"]
    block = np.repeat(np.arange(len(mix)), [int(e["weight"]) for e in mix])
    for _ in itertools.count():
        for j in rng.permutation(block):
            yield requests.make(rng, mix[int(j)], traffic)


def window_requests(traffic: dict, seed: int, seconds: float, requests,
                    per_client: int = 64) -> List[dict]:
    """The first ``per_client`` requests of every client (a closed loop's
    count depends on how fast it is answered)."""
    out = []
    for c in range(int(traffic["clients"])):
        s = stream(traffic, seed, c, requests)
        out += [next(s) for _ in range(per_client)]
    return out


def drive_window(traffic: dict, seed: int, seconds: float, requests, system,
                 on_open: Optional[Callable[[], object]] = None) -> tuple:
    """Run the window; returns (records, t0, t_close)."""
    streams = [stream(traffic, seed, c, requests) for c in range(int(traffic["clients"]))]
    if on_open:
        on_open()
    return drive.run_closed(lambda spec: requests.call(system, spec), streams, seconds)
