"""The harness on the CPU: every name in BENCHMARK.json resolves to its
files, a new arrival pattern or a new deployment is new files and nothing
else, the corpus module gives what the direct calls gave, a traced run
hands its spans to the readers and leaves the recorder off, the generators
keep their schedules and time from due time, and a run refuses any
platform but a TPU."""

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

from repro.core.spans import RECORDER

from perfbench import run as R
from perfbench.lib import corpus as C
from perfbench.lib import drive, traffic as traffic_mod
from perfbench.lib.bench import ROOT, Benchmark
from perfbench.lib.embedding import HashEmbedding
from perfbench.lib.reference import Reference, answers

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
    mod = BENCH.corpus(body)
    assert all(callable(getattr(mod, f)) for f in ("embedding", "generate", "reference"))
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


TINY = {"rows": 3_000, "sessions": 50}


def cpu(chips):
    return {"platform": "cpu", "kind": "cpu", "count": chips}


#: a deployment the benchmark does not have: seeded unit rows at 768-d with
#: no timestamps, its own embedding over a 768-d parent space, and its own
#: reference (plain cosine top-k in float64, or bfloat16 for the control)
UNIT_CORPUS = '''
import dataclasses

import ml_dtypes
import numpy as np

from perfbench.lib.embedding import HashEmbedding, normalize_rows
from perfbench.lib.reference import Scored, top_rows, unit


@dataclasses.dataclass
class Corpus:
    matrix: np.ndarray
    timestamps = None

    @property
    def n(self):
        return int(self.matrix.shape[0])

    @property
    def ids(self):
        return np.arange(self.n, dtype=np.int64)


def embedding(cfg):
    return HashEmbedding(int(cfg["dim"]), full_dim=int(cfg["dim"]))


def generate(cfg, seed, embedding):
    rng = np.random.default_rng([seed, 0])
    return Corpus(normalize_rows(rng.standard_normal((int(cfg["rows"]), embedding.dim),
                                                     dtype=np.float32)))


class Reference:
    def __init__(self, corpus, embedding, precision):
        self.corpus, self.embedding, self.precision = corpus, embedding, precision

    def _vec(self, v):
        if self.precision == "bf16":
            return np.asarray(v, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)
        return np.asarray(v, np.float64)

    def rows(self, ids):
        return self._vec(self.corpus.matrix[np.asarray(ids, np.int64)])

    def score(self, specs):
        q = np.stack([unit(self.embedding(s["similar"])) for s in specs], axis=1)
        dots = self._vec(self.corpus.matrix) @ self._vec(q)
        return [Scored(dots[:, j], self.corpus.n) for j in range(len(specs))]

    def answer(self, spec, scored):
        s = scored.scores
        return [(int(i), float(s[i])) for i in top_rows(s, spec["k"])]


def reference(corpus, embedding, precision):
    return Reference(corpus, embedding, precision)
'''

#: a request kind that draws its own query text (seeded tokens, none of
#: them agent-history words) and serves it through the search surface
UNIT_REQUESTS = '''
from perfbench.lib.traffic import base_spec
from perfbench.requests.search import call, submit, warm  # noqa: F401

SURFACE = "search"


def make(rng, entry, traffic):
    spec = base_spec(entry, SURFACE, traffic["k"])
    spec["similar"] = " ".join(f"t{int(x)}" for x in rng.integers(0, 5000, size=4))
    spec["tokens"] = spec["text"] = f"similar:{spec['similar']}"
    return spec
'''

#: per-layer metrics of the new cell: one reads the system's counters after
#: the window, one the program's spans recorded over it
UNIT_COUNTER_METRIC = '''
def read(run):
    engine = run.counters.get("engine") or {}
    served = engine.get("requests_served")
    return served / engine["batches_served"] if served else None
'''
UNIT_SPAN_METRIC = '''
def read(run):
    if run.spans is None:
        return None
    ms = [(sp.end_ns - sp.start_ns) * 1e-6 for sp in run.spans if sp.name == "engine.request"]
    return sum(ms) / len(ms) if ms else None
'''


def test_a_new_deployment_is_new_files_only(tmp_path):
    """A copy of the benchmark gains a 768-d corpus with no timestamps, its
    configuration, a request kind with its own query text, a mix, limits, a
    cell and two per-layer metrics (counters and spans): all new files plus
    entries, and a traced tiny run of it on the CPU reads correct and
    reports both metrics."""
    root = tmp_path / "bench"
    pkg = root / "perfbench"
    shutil.copytree(ROOT / "perfbench", pkg, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = {p: p.read_bytes() for p in pkg.rglob("*") if p.is_file()}
    new = {
        "corpora/unit_rows.py": UNIT_CORPUS,
        "requests/unit_search.py": UNIT_REQUESTS,
        "metrics/requests_per_batch.unit.py": UNIT_COUNTER_METRIC,
        "metrics/request_ms.unit.py": UNIT_SPAN_METRIC,
        "configs/unit_768.json": json.dumps({
            "name": "unit_768", "corpus": "unit_rows", "system": "engine", "engine": "jit-jax",
            "max_batch": 8, "rows": 2_000, "dim": 768, "now": 1_770_000_000}),
        "traffic/unit_closed4.json": json.dumps({
            "source": "a test", "generator": "closed_loop", "requests": "unit_search",
            "clients": 4, "k": 10, "check_sample": 16, "mix": [{"name": "plain", "weight": 1}]}),
    }
    for rel, text in new.items():
        assert not (pkg / rel).exists()
        (pkg / rel).write_text(text)
    shutil.copy(BENCH.limits_path("h1m_search_closed64"), pkg / "limits" / "unit_768_closed4.json")
    doc = json.loads(json.dumps(DOC))
    doc["configs"].append({"name": "unit_768", "source": "a test", "reduced": [],
                           "file": "perfbench/configs/unit_768.json", "why": "a test"})
    doc["workloads"].append({"name": "unit_768_closed4", "config": "unit_768",
                             "traffic": "unit_closed4", "chips": 1, "why": "a test"})
    for m in doc["end_to_end"]:
        if "h1m_search_closed64" in m.get("workloads", ()):
            m["workloads"].append("unit_768_closed4")
    doc["per_layer"] += [
        {"name": n, "unit": u, "better": "lower", "source": src, "layer": "a test",
         "moves": "search_p95_ms", "workloads": ["unit_768_closed4"]}
        for n, u, src in (("requests_per_batch.unit", "requests", "program_counter"),
                          ("request_ms.unit", "ms", "program_span"))]
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    assert all(p.read_bytes() == b for p, b in before.items())  # nothing edited

    res = R.run_cell("unit_768_closed4", 2**31 + 29, 1.0, True, device_check=cpu,
                     bench=Benchmark(root))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"requests_per_batch.unit", "request_ms.unit"}
    assert res["metrics"]["requests_per_batch.unit"]["value"] >= 1.0
    assert res["metrics"]["request_ms.unit"]["value"] > 0.0
    assert not RECORDER.on


@pytest.mark.parametrize("seed", [5, 2**31 + 77])
@pytest.mark.parametrize("config", ["agent_history_1m", "agent_history_240k"])
def test_agent_history_module_gives_the_direct_calls(config, seed):
    """The configuration's corpus module gives, bit for bit, what the
    harness built before it named one: the corpus, the requests of every
    cell on it, and the reference's answers on a sample of them."""
    cfg = dict(BENCH.config(config), **TINY)
    mod = BENCH.corpus(cfg)
    emb = mod.embedding(cfg)
    got = mod.generate(cfg, seed, emb)
    emb0 = HashEmbedding(int(cfg["dim"]))
    want = C.generate(cfg, seed, emb0)
    for f in ("matrix", "timestamps", "words", "ctype", "project", "session", "topic"):
        assert getattr(got, f).tobytes() == getattr(want, f).tobytes(), f
    cells = [w for w in DOC["workloads"] if w["config"] == config]
    assert cells
    for cell in cells:
        traffic = BENCH.traffic(cell["traffic"])
        req = BENCH.requests(traffic)
        warm = traffic_mod.warm_requests(traffic, seed, req)
        specs = BENCH.generator(traffic).window_requests(traffic, seed, 1.0, req)
        specs = [r["spec"] for r in drive.sample([{"spec": s} for s in specs], 6, seed)]
        specs += list(warm.values())
        for precision in ("f64", "bf16"):
            a = answers(mod.reference(got, emb, precision), specs)
            b = answers(Reference(want, emb0, precision=precision), specs)
            assert a == b, (cell["name"], precision)


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_recorder_is_off_after_a_run(trace):
    """A traced run records the window's spans and hands them to the span
    readers; an untraced one never turns the recorder on (no span site
    records); neither leaves it on."""
    RECORDER.drain()
    res = R.run_cell("h1m_search_single", 2**31 + 41, 1.0, trace, device_check=cpu,
                     config_overrides=TINY)
    assert res["correct"], res["checks"]
    assert not RECORDER.on
    spans = {"admit_ms_per_query.search", "queue_ms_per_query.search",
             "dispatch_ms_per_query.search", "upload_bytes_per_query.search",
             "tail_ms_per_query.search"}
    if trace:
        assert spans <= set(res["metrics"])
        assert res["metrics"]["upload_bytes_per_query.search"]["value"] > 0
        assert "idle_by_span" in res["breakdown"]
    else:
        assert RECORDER.drain() == []
        assert not spans & set(res["metrics"]) and "breakdown" not in res


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
