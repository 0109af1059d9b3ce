"""The harness on the CPU: every name in BENCHMARK.json resolves to its
files, a new arrival pattern is a new file and nothing else, the
generators keep their schedules and time from due time, and a run refuses
any platform but a TPU."""

import asyncio
import json
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from perfbench import run as R
from perfbench.lib import drive
from perfbench.lib.bench import ROOT, Benchmark

BENCH = Benchmark()
DOC = BENCH.doc
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    assert DOC["command"] == ["python3", "perfbench/run.py"] and DOC["paths"] == ["perfbench"]
    assert 1 <= DOC["run_seconds"] <= 51
    assert len(json.dumps(DOC)) < 64 * 1024


@pytest.mark.parametrize("cfg", DOC["configs"], ids=lambda c: c["name"])
def test_configuration_resolves(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"} and NAME.match(cfg["name"])
    assert cfg["file"].startswith("perfbench/") and (ROOT / cfg["file"]).is_file()
    body = BENCH.config(cfg["name"])
    assert body["name"] == cfg["name"] and BENCH.system_path(body["system"]).is_file()
    assert any(w["config"] == cfg["name"] for w in DOC["workloads"])


@pytest.mark.parametrize("cell", DOC["workloads"], ids=lambda w: w["name"])
def test_cell_resolves(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    BENCH.config_entry(cell["config"])
    traffic = BENCH.traffic(cell["traffic"])
    assert traffic["source"] and "\n" not in traffic["source"]
    gen, req = BENCH.generator(traffic), BENCH.requests(traffic)
    assert callable(gen.drive_window) and callable(gen.window_requests)
    assert all(callable(getattr(req, f)) for f in ("make", "call", "submit", "warm"))
    assert req.SURFACE in ("search", "sql")
    limits = BENCH.limits(cell["name"])
    assert set(limits) == {"rank_gap", "score_gap", "set_miss", "unanswered"}
    e2e = [m["name"] for m in BENCH.metrics_for(cell["name"], "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = BENCH.metrics_for(cell["name"], "per_layer")
    assert layer and all(m["moves"] in e2e for m in layer)


@pytest.mark.parametrize("metric", DOC["end_to_end"] + DOC["per_layer"], ids=lambda m: m["name"])
def test_metric_resolves(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert callable(BENCH.reader(metric["name"]).read)
    if metric in DOC["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert metric["moves"] in {m["name"] for m in DOC["end_to_end"]}
        layers = {m["layer"] for m in DOC["per_layer"]}
        assert metric["layer"] in layers


def test_layer_names_are_in_perf_md():
    perf = (ROOT / "PERF.md").read_text()
    for layer in {m["layer"] for m in DOC["per_layer"]}:
        assert layer in perf


def test_open_schedule_is_the_same_work_for_every_seed():
    traffic = BENCH.traffic("search_open")
    gen, req = BENCH.generator(traffic), BENCH.requests(traffic)
    d1, s1 = gen.schedule(traffic, 1, 5.0, req)
    d2, s2 = gen.schedule(traffic, 2**31 + 99, 5.0, req)
    n = round(traffic["rate"] * 5.0)
    assert len(d1) == len(d2) == n and d1[0] == d2[0] == 0.0 and d1[-1] < 5.0
    np.testing.assert_allclose(np.sort(np.diff(np.append(d1, 5.0))),
                               np.sort(np.diff(np.append(d2, 5.0))), atol=1e-9)
    assert not np.allclose(d1, d2)
    kinds1 = sorted(s["kind"] for s in s1)
    assert kinds1 == sorted(s["kind"] for s in s2)
    counts = {k: kinds1.count(k) for k in set(kinds1)}
    assert max(counts.values()) - min(counts.values()) <= 1
    assert len({s["text"] for s in s1}) == n  # no two requests alike


def test_closed_stream_keeps_the_mix():
    traffic = BENCH.traffic("sql_agent")
    gen, req = BENCH.generator(traffic), BENCH.requests(traffic)
    stream = gen.stream(traffic, 3, 0, req)
    kinds = [next(stream)["kind"] for _ in range(100)]
    assert kinds.count("composed") == 40 and kinds.count("filtered") == 30
    first = [next(gen.stream(traffic, 3, 0, req))["text"] for _ in range(2)]
    assert first[0] == first[1]


def test_sql_rendering():
    traffic = BENCH.traffic("sql_agent")
    req = BENCH.requests(traffic)
    rng = np.random.default_rng(0)
    comp, filt, hyb = (req.make(rng, e, traffic) for e in traffic["mix"])
    assert re.fullmatch(r"SELECT v\.id, v\.score FROM vec_ops\('similar:[a-z0-9 ]+ "
                        r"suppress:[a-z ]+ from:[a-z ]+ to:[a-z ]+ decay:30 diverse "
                        r"pool:500'\) v ORDER BY v\.score DESC, v\.id", comp["text"])
    assert "FROM chunks WHERE" in filt["text"] and "''" in filt["text"]
    assert hyb["text"].startswith("SELECT v.id, v.score FROM hybrid_search('")
    assert hyb["text"].endswith(", 0.6) v ORDER BY v.score DESC, v.id")


#: an arrival pattern the benchmark does not have: on/off bursts of an open loop
BURST_GENERATOR = '''
import numpy as np
from perfbench.lib import drive


def schedule(traffic, seed, seconds, requests):
    on, off, rate = traffic["on_s"], traffic["off_s"], traffic["rate"]
    due, t = [], 0.0
    while t < seconds:
        due += list(np.arange(t, min(t + on, seconds), 1.0 / rate))
        t += on + off
    rng = np.random.default_rng([seed, 1])
    mix = traffic["mix"]
    specs = [requests.make(rng, mix[i % len(mix)], traffic) for i in range(len(due))]
    return np.asarray(due), specs


def window_requests(traffic, seed, seconds, requests):
    return schedule(traffic, seed, seconds, requests)[1]


def drive_window(traffic, seed, seconds, requests, system, on_open=None):
    due, specs = schedule(traffic, seed, seconds, requests)
    if on_open:
        on_open()
    return drive.run_open(lambda spec: requests.submit(system, spec), due, specs, seconds)
'''


def test_a_new_arrival_pattern_is_new_files_only(tmp_path):
    """A copy of the benchmark gains a burst generator, a mix that names it,
    a cell and its limits: all new files plus the cell's entry, and a whole
    tiny run of it on the CPU reads correct."""
    root = tmp_path / "bench"
    shutil.copytree(ROOT / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = {p: p.read_bytes() for p in (root / "perfbench").rglob("*") if p.is_file()}
    (root / "perfbench" / "traffic" / "burst_loop.py").write_text(BURST_GENERATOR)
    mix = dict(BENCH.traffic("search_open"), source="a test", generator="burst_loop",
               rate=40, on_s=0.2, off_s=0.3)
    (root / "perfbench" / "traffic" / "search_burst.json").write_text(json.dumps(mix))
    shutil.copy(BENCH.limits_path("h1m_search_closed64"),
                root / "perfbench" / "limits" / "h1m_search_burst.json")
    doc = json.loads(json.dumps(DOC))
    doc["workloads"].append({"name": "h1m_search_burst", "config": "agent_history_1m",
                             "traffic": "search_burst", "chips": 1, "why": "a test"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "h1m_search_closed64" in m.get("workloads", ()):
            m["workloads"].append("h1m_search_burst")
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    assert all(p.read_bytes() == b for p, b in before.items())  # nothing edited

    res = R.run_cell("h1m_search_burst", 2**31 + 17, 1.0, False,
                     device_check=lambda c: {"platform": "cpu", "kind": "cpu", "count": c},
                     config_overrides={"rows": 3_000, "sessions": 50}, bench=Benchmark(root))
    assert res["correct"], res["checks"]
    assert res["attempted"] == 16 and res["failed"] == 0
    assert {"search_p95_ms", "search_qps", "setup_s"} <= set(res["metrics"])


def test_open_loop_times_from_due_and_reports_lateness():
    """A server that stalls 0.2 s on the first request: the requests due
    during the stall are sent late (the generator waits on the same loop)
    and their latency counts from when they were due."""
    async def submit(spec):
        if spec["i"] == 0:
            time.sleep(0.2)  # blocks the loop: the generator runs late
        await asyncio.sleep(0.01)
        return [(spec["i"], 1.0)]

    due = np.arange(10) * 0.02
    specs = [{"i": i, "kind": "k"} for i in range(10)]
    records, t0, close = drive.run_open(submit, due, specs, 0.2)
    s = drive.summarize(records, t0, close)
    assert s["attempted"] == 10 and s["failed"] == 0
    late = [(r["start"] - r["due"]) for r in records]
    assert late[0] < 0.01 and late[1] > 0.15
    assert s["late_max_ms"] > 150
    lat = [(r["end"] - r["due"]) for r in records]
    assert lat[1] > 0.17  # charged from due time, not from the late send
    assert s["p95_ms"] > 170


def test_closed_loop_counts_failures():
    calls = []

    def call(spec):
        calls.append(spec)
        if len(calls) % 3 == 0:
            raise RuntimeError("boom")
        time.sleep(0.005)
        return []

    streams = [iter({"kind": "k"} for _ in iter(int, 1)) for _ in range(2)]
    records, t0, close = drive.run_closed(call, streams, 0.2)
    s = drive.summarize(records, t0, close)
    assert s["attempted"] == len(calls) and 0 < s["failed"] < s["attempted"]


def test_run_refuses_a_platform_that_is_not_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "perfbench" / "run.py"), "--workload",
                          "h1m_search_single", "--seed", "3000000000", "--seconds", "1",
                          "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
                         text=True, timeout=240)
    assert out.returncode != 0
    assert "not a TPU" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())
