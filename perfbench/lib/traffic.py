"""What every traffic mix shares: text specs, the query grammar, exact counts.

A traffic file (``perfbench/traffic/<mix>.json``) is data only:

- ``source``: where the mix and its query shapes come from;
- ``generator``: the arrivals, a module ``perfbench/traffic/<generator>.py``
  with ``drive_window`` (run the window) and ``window_requests`` (what a window
  sends, for the control) — ``open_loop`` (a fixed ``rate``) and
  ``closed_loop`` (``clients`` that each wait for their answer);
- ``requests``: what is sent, a module ``perfbench/requests/<requests>.py``
  with ``make`` (a request from a mix entry), ``call`` / ``submit`` (send it
  to the deployment) and ``warm`` — ``search`` (the batched engine's
  ``search(tokens, k)``) and ``sql`` (``flex_search`` statements);
- ``k`` for ``search``; ``check_sample``, how many of the window's requests
  the reference checks;
- ``mix``: entries with an integer ``weight`` and the request's parts:
  ``similar`` / ``suppress`` / ``from`` / ``to`` text specs, ``decay``,
  ``diverse``, ``pool``, a list of Phase-1 ``filter`` predicates to draw
  from, or ``hybrid`` (the vector weight) with a ``keyword`` text spec.

A text spec is ``{"words": [lo, hi]}`` — lo to hi-1 words drawn from one
topic's vocabulary — optionally with ``"nonce": true`` (a seeded token that
makes the text unique), or ``{"choose": [phrases]}``.

A new arrival pattern or request kind is a new module beside the others,
named by a new traffic file; nothing here changes.  The generators hold
the mix to its exact proportions for every seed; the words and a filtered
statement's predicate come from the seed.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from perfbench.lib.corpus import TOPICS


def text(rng: np.random.Generator, spec: dict) -> str:
    if "choose" in spec:
        return str(spec["choose"][int(rng.integers(len(spec["choose"])))])
    lo, hi = spec["words"]
    vocab = TOPICS[int(rng.integers(len(TOPICS)))][1]
    words = [vocab[int(rng.integers(len(vocab)))] for _ in range(int(rng.integers(lo, hi)))]
    if spec.get("nonce"):
        words.append(f"q{int(rng.integers(1 << 32)):08x}")
    return " ".join(words)


def num(x: float) -> str:
    return str(int(x)) if float(x).is_integer() else repr(float(x))


def base_spec(entry: dict, surface: str, k) -> dict:
    """A request's structured spec (what the reference reads), unfilled."""
    return {"kind": entry["name"], "surface": surface, "k": k, "similar": None,
            "suppress": [], "from": None, "to": None, "decay": entry.get("decay"),
            "diverse": bool(entry.get("diverse")), "pool": entry.get("pool"),
            "filter": None, "hybrid": entry.get("hybrid"), "keyword": None}


def modulated(rng: np.random.Generator, entry: dict, surface: str, k) -> dict:
    """A ``vec_ops`` request: its spec, with ``tokens`` in the query grammar."""
    spec = base_spec(entry, surface, k)
    spec["similar"] = text(rng, entry["similar"])
    spec["suppress"] = [text(rng, x) for x in entry.get("suppress", ())]
    if "from" in entry:
        spec["from"], spec["to"] = text(rng, entry["from"]), text(rng, entry["to"])
    if entry.get("filter"):
        spec["filter"] = dict(entry["filter"][int(rng.integers(len(entry["filter"])))])
    parts = [f"similar:{spec['similar']}"]
    parts += [f"suppress:{x}" for x in spec["suppress"]]
    if spec["from"] is not None:
        parts += [f"from:{spec['from']}", f"to:{spec['to']}"]
    if spec["decay"] is not None:
        parts.append(f"decay:{num(spec['decay'])}")
    if spec["diverse"]:
        parts.append("diverse")
    if spec["pool"] is not None:
        parts.append(f"pool:{int(spec['pool'])}")
    spec["tokens"] = " ".join(parts)
    return spec


def exact_counts(weights: Sequence[int], n: int) -> List[int]:
    """Largest-remainder split of n by weights."""
    w = np.asarray(weights, np.float64)
    raw = n * w / w.sum()
    out = np.floor(raw).astype(int)
    for j in np.argsort(-(raw - out), kind="stable")[: n - int(out.sum())]:
        out[j] += 1
    return [int(x) for x in out]


def warm_requests(traffic: dict, seed: int, requests) -> Dict[str, dict]:
    """One request of each mix entry, from a stream the window never uses."""
    rng = np.random.default_rng([seed, 3])
    return {e["name"]: requests.make(rng, e, traffic) for e in traffic["mix"]}
