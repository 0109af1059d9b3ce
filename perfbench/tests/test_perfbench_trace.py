"""The trace reduction, the peaks table and the per-layer readers, on
synthetic traces and one real (CPU) profile."""

import glob
import time

import pytest

from perfbench.lib import layers, tracing


def test_unknown_device_kind_raises():
    assert tracing.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        tracing.peaks("TPU v9 imaginary")


def synthetic():
    events = {"/device:TPU:0": [("fusion.1", 1.0, 1.2), ("top_k", 1.15, 1.4),
                                ("fusion.1", 2.0, 2.1), ("while", 2.5, 2.9),
                                ("outside", 9.0, 9.5)]}
    return tracing.reduce_events(events, (1.0, 3.0))


def test_busy_is_the_union_and_ops_are_summed():
    t = synthetic()
    assert t.busy == [(1.0, 1.4), (2.0, 2.1), (2.5, 2.9)]
    assert t.busy_s == pytest.approx(0.9)
    assert t.window_s == pytest.approx(2.0)
    assert t.op_seconds["fusion.1"] == pytest.approx(0.3)
    assert t.op_seconds["top_k"] == pytest.approx(0.25)
    assert "outside" not in t.op_seconds
    assert t.gaps() == [(1.4, 2.0), (2.1, 2.5), (2.9, 3.0)]
    assert t.busy_within(1.3, 2.05) == pytest.approx(0.15)
    assert tracing.top_ops(t, 1) == [["while", pytest.approx(0.4)]]


def rec(kind, start, end):
    return {"spec": {"kind": kind}, "start": start, "end": end, "due": start,
            "error": None}


def test_gaps_are_named_by_the_request_in_flight():
    t = synthetic()
    records = [rec("composed", 1.0, 1.5), rec("filtered", 1.5, 2.45), rec("hybrid", 2.9, 3.0)]
    named = tracing.name_gaps(t, records)
    assert named[0] == ["filtered", pytest.approx(0.6)]
    assert ["filtered", pytest.approx(0.4)] in named


class FakeRun:
    surface = "search"
    cfg = {"rows": 1_000_000, "dim": 128}
    device = {"kind": "TPU v5 lite"}

    def __init__(self, records, trace):
        self.records, self.trace = records, trace


def test_layer_readers():
    t = synthetic()
    records = [rec("a", 1.0, 1.5), rec("a", 1.5, 2.2), rec("a", 2.2, 2.95), rec("a", 2.95, 3.5)]
    run = FakeRun(records, t)
    assert layers.device_ms_per_request(run, "search") == pytest.approx(900 / 3)
    assert layers.device_ms_per_request(run, "sql") is None
    host = ((0.5 - 0.4) + (0.7 - 0.1) + (0.75 - 0.4)) / 3 * 1e3
    assert layers.host_ms_per_request(run, "search") == pytest.approx(host)
    least = 3 * 1_000_000 * 128 * 4 / 819e9
    assert layers.scan_roofline(run, "search") == pytest.approx(least / 0.9 * 100)
    run.trace = None
    assert layers.scan_roofline(run, "search") is None


def test_real_profile_aligns_the_clock_marker(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    tracer = tracing.Tracer(str(tmp_path), 0.0, 0.2).begin()
    t_end = time.perf_counter() + 0.3
    while time.perf_counter() < t_end:
        f(x).block_until_ready()
    trace = tracer.join()
    assert glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    lo, hi = trace.window
    assert tracer.marker_pc_ns * 1e-9 == pytest.approx(lo)
    assert 0.15 < hi - lo < 1.0
    assert trace.busy_s == 0.0  # the CPU backend writes no device plane
