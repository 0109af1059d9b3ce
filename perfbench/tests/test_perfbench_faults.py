"""Whole runs at a tiny size on the CPU, past the harness's look for a
chip: a sound run reads ``correct``; the same run with the timed path
broken underneath reads not correct; and the control (the reference in
bfloat16 in the program's place) reads not correct for every cell."""

import numpy as np
import pytest

from perfbench import control
from perfbench import run as R
from perfbench.lib import check
from perfbench.lib.bench import Benchmark

TINY = {"rows": 3_000, "sessions": 50}


def cpu(chips):
    return {"platform": "cpu", "kind": "cpu", "count": chips}


def run(cell, seed=2**31 + 321, seconds=1.0):
    return R.run_cell(cell, seed, seconds, False, device_check=cpu, config_overrides=TINY)


def alter_first_row(monkeypatch):
    """An answer altered where it is produced: the engine's host tail hands
    back another row in place of each answer's first."""
    import repro.serve.engine as engine

    real = engine.finalize_segment_candidates

    def altered(*args, **kwargs):
        out = real(*args, **kwargs)
        return [[((i + 7919) % TINY["rows"], s) if j == 0 else (i, s)
                 for j, (i, s) in enumerate(res)] for res in out]

    monkeypatch.setattr(engine, "finalize_segment_candidates", altered)


def cross_half_the_batch(monkeypatch):
    """Half of each batch left out: requests in its second half get the
    answers of the first half's requests."""
    from repro.serve.engine import BatchedRetrievalEngine

    real = BatchedRetrievalEngine._finish
    seen = {}

    def finish(self, req, result):
        key = id(self)
        first = seen.setdefault(key, [])
        if len(first) < 1 or len(first) % 2 == 0:
            first.append(result)
            return real(self, req, result)
        first.append(result)
        return real(self, req, first[-2])

    monkeypatch.setattr(BatchedRetrievalEngine, "_finish", finish)


@pytest.mark.parametrize("cell", ["h1m_search_single", "h240k_sql_agent"])
def test_sound_run_is_correct(cell):
    res = run(cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert {"setup_s"} < set(res["metrics"])


@pytest.mark.parametrize("cell", ["h1m_search_single", "h240k_sql_agent"])
def test_altered_answer_is_not_correct(cell, monkeypatch):
    alter_first_row(monkeypatch)
    res = run(cell)
    assert not res["correct"], res["checks"]


def test_crossed_batch_is_not_correct(monkeypatch):
    cross_half_the_batch(monkeypatch)
    res = run("h1m_search_closed64")
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", [w["name"] for w in Benchmark().doc["workloads"]])
def test_control_is_not_correct(cell):
    numbers = control.control_numbers(cell, 77, seconds=1.0, config_overrides=TINY)
    limits = Benchmark().limits(cell)
    assert not check.verdict(numbers, limits), numbers
    assert np.isfinite(numbers["score_gap"])
