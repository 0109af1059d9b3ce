"""The span readers (``lib/layer_spans.py`` and the metric files that call
it) on synthetic spans and a synthetic trace, and the stage scope read off
a profile event."""

import pytest

from perfbench.lib import layer_spans as L
from perfbench.lib import tracing
from perfbench.lib.bench import Benchmark
from repro.core.spans import Span

BENCH = Benchmark()
S = 1_000_000_000  # ns per second

SEARCH = ["admit_ms_per_query.search", "queue_ms_per_query.search",
          "dispatch_ms_per_query.search", "tail_ms_per_query.search",
          "select_ms_per_query.search", "upload_bytes_per_query.search"]
SQL = ["phase1_ms_per_stmt.sql", "fts_ms_per_stmt.sql", "queue_ms_per_stmt.sql",
       "materialize_ms_per_stmt.sql"]


def sp(name, a, b, span_id=0, parent=None, rid=None, **attrs):
    return Span(name, span_id, parent, rid, "t", int(a * S), int(b * S), attrs)


def trace():
    # device busy [1.2, 1.4] and [2.0, 2.1] in a [1.0, 3.0] slice
    events = {"/device:TPU:0": [("fusion", 1.2, 1.4), ("sort", 2.0, 2.1)]}
    return tracing.reduce_events(events, (1.0, 3.0))


def rec(start, end):
    return {"spec": {"kind": "k"}, "start": start, "end": end, "due": start, "error": None}


class FakeRun:
    device = {"kind": "TPU v5 lite"}
    cfg = {"rows": 1000, "dim": 8}

    def __init__(self, surface, spans, scope_seconds=None):
        self.surface, self.trace = surface, trace()
        self.records = [rec(1.0, 1.9), rec(1.9, 2.8)]  # two answered in the slice
        self.spans, self.scope_seconds = spans, scope_seconds


def search_spans():
    return [sp("engine.request", 1.0, 1.9, 1, rid=0),
            sp("engine.admit", 1.0, 1.1, 2, 1, 0),
            sp("engine.queue", 1.1, 1.15, 3, 1, 0),
            sp("engine.device", 1.15, 1.5, 4, requests=[0], upload_bytes=600),
            sp("engine.tail", 1.6, 1.9, 5, requests=[0]),
            sp("engine.request", 1.9, 2.8, 6, rid=1),
            sp("engine.admit", 1.9, 1.95, 7, 6, 1),
            sp("engine.queue", 1.95, 1.99, 8, 6, 1),
            sp("engine.device", 1.99, 2.2, 9, requests=[1], upload_bytes=400),
            sp("engine.tail", 2.2, 2.8, 10, requests=[1]),
            sp("engine.admit", 3.5, 4.0, 11)]  # outside the slice: not counted


def test_search_readers():
    run = FakeRun("search", search_spans(), {"score": 0.2, "select": 0.1})
    got = {m: BENCH.reader(m).read(run) for m in SEARCH}
    assert got["admit_ms_per_query.search"] == pytest.approx((100 + 50) / 2)
    assert got["queue_ms_per_query.search"] == pytest.approx((50 + 40) / 2)
    # device spans 350 + 210 ms minus the 200 + 100 ms busy inside them
    assert got["dispatch_ms_per_query.search"] == pytest.approx((150 + 110) / 2)
    assert got["tail_ms_per_query.search"] == pytest.approx((300 + 600) / 2)
    assert got["select_ms_per_query.search"] == pytest.approx(100 / 2)
    assert got["upload_bytes_per_query.search"] == pytest.approx(1000 / 2)
    assert all(BENCH.reader(m).read(run) is None for m in SQL)


def test_readers_find_nothing_without_spans():
    run = FakeRun("search", None)  # an untraced run: no spans, no scopes
    assert all(BENCH.reader(m).read(run) is None for m in SEARCH + SQL)
    run = FakeRun("search", search_spans())
    run.trace = None
    assert all(BENCH.reader(m).read(run) is None for m in SEARCH)


def test_sql_readers():
    spans = [sp("sql.statement", 1.0, 1.9, 1),
             sp("sql.phase1", 1.0, 1.1, 2, 1),
             sp("sql.plan", 1.1, 1.3, 3, 1),
             sp("sql.fts", 1.15, 1.25, 4, 3),
             sp("engine.request", 1.3, 1.6, 5, 1, 7),
             sp("engine.queue", 1.32, 1.4, 6, 5, 7),
             sp("sql.materialize", 1.6, 1.7, 7, 1),
             sp("sql.select", 1.7, 1.75, 8, 1),
             sp("sql.statement", 1.9, 2.8, 9),
             sp("engine.request", 2.0, 2.5, 10, None, 8),  # owned by no statement
             sp("engine.queue", 2.0, 2.3, 11, 10, 8)]
    run = FakeRun("sql", spans)
    got = {m: BENCH.reader(m).read(run) for m in SQL}
    assert got["phase1_ms_per_stmt.sql"] == pytest.approx(100 / 2)
    assert got["fts_ms_per_stmt.sql"] == pytest.approx(100 / 2)
    assert got["queue_ms_per_stmt.sql"] == pytest.approx(80 / 2)
    assert got["materialize_ms_per_stmt.sql"] == pytest.approx(150 / 2)
    assert all(BENCH.reader(m).read(run) is None for m in SEARCH)


def test_idle_by_span():
    t = trace()  # idle: [1.0, 1.2], [1.4, 2.0], [2.1, 3.0]
    spans = [sp("a", 1.1, 1.5), sp("a", 1.45, 1.6), sp("b", 2.5, 3.5),
             sp("engine.request", 1.0, 2.4), sp("sql.statement", 1.0, 2.4)]
    records = [rec(1.0, 2.4)]
    got = L.idle_by_span(t, records, spans)
    assert got["a"] == pytest.approx(0.1 + 0.2)   # [1.1, 1.2] + [1.4, 1.6]
    assert got["b"] == pytest.approx(0.5)         # [2.5, 3.0]
    assert got["engine.request"] == got["sql.statement"] == pytest.approx(0.2 + 0.6 + 0.3)
    # the umbrella spans name no layer, so they leave ``none`` as it is:
    # idle inside the record [1.0, 2.4] with no span open:
    # [1.0, 1.1] + [1.6, 2.0] + [2.1, 2.4]
    assert got["none"] == pytest.approx(0.1 + 0.4 + 0.3)


def test_interval_arithmetic():
    xs, ys = [(0.0, 2.0), (3.0, 5.0)], [(1.0, 4.0), (4.5, 6.0)]
    assert L.intersect(xs, ys) == [(1.0, 2.0), (3.0, 4.0), (4.5, 5.0)]
    assert L.clipped([(0.0, 2.0), (5.0, 6.0)], 1.0, 5.5) == [(1.0, 2.0), (5.0, 5.5)]
    assert L.length(L.intersect(xs, [])) == 0.0


@pytest.mark.parametrize("texts,scope", [
    (["jit(fused_select)/select/top_k", "%sort.2 = f32[] sort()"], "select"),
    (["%f = fusion(), metadata={op_name=\"jit(f)/score/dot\"}"], "score"),
    (["jit(fused_select)/mmr/while/body/argmax"], "mmr"),
    (["jit(f)/rescore/x", "select", "%select.3 = f32[] select()"], None),
], ids=["tf_op", "long_name", "nested", "none"])
def test_scope_of_an_op(texts, scope):
    assert L.scope_of(texts) == scope


def _pb(*fields):
    """A protobuf message from ``(number, value)``: int -> varint, bytes or
    str -> length-delimited."""
    def varint(n):
        out = b""
        while True:
            b, n = n & 0x7F, n >> 7
            out += bytes([b | (0x80 if n else 0)])
            if not n:
                return out
    out = b""
    for num, val in fields:
        if isinstance(val, int):
            out += varint(num << 3) + varint(val)
        else:
            val = val.encode() if isinstance(val, str) else val
            out += varint(num << 3 | 2) + varint(len(val)) + val
    return out


def test_op_scopes_read_the_event_metadata(tmp_path):
    def stat_meta(i, name):
        return (5, _pb((1, i), (2, _pb((1, i), (2, name)))))

    tf_op, category, ref = 3, 4, 9
    ops = [
        _pb((1, 1), (2, "%fusion.1 = fusion()"), (4, "fusion.1"),
            (5, _pb((1, tf_op), (5, "jit(fused_select)/score/dot_general:")))),
        # lax.top_k's sort keeps no metadata: its category names the stage
        _pb((1, 2), (2, "%sort.2 = sort()"), (5, _pb((1, category), (5, "sort")))),
        _pb((1, 3), (2, "%while.6 = while()"), (5, _pb((1, tf_op), (7, ref)))),
        _pb((1, 4), (2, "%copy-start = copy-start()"),
            (5, _pb((1, category), (5, "copy-start")))),
    ]
    device = _pb((1, 7), (2, "/device:TPU:0"),
                 *[(4, _pb((1, i + 1), (2, op))) for i, op in enumerate(ops)],
                 stat_meta(tf_op, "tf_op"), stat_meta(category, "hlo_category"),
                 stat_meta(ref, "jit(fused_select)/mmr/while"), (3, _pb((2, "XLA Ops"))))
    host = _pb((2, "/host:CPU"), (4, _pb((1, 1), (2, _pb((2, "x"), (5, _pb((5, "a/score/b"))))))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb((1, host), (1, device), (4, "hostname")))
    assert L.op_scopes(str(path)) == {"/device:TPU:0": {
        "%fusion.1 fusion": "score", "%sort.2 sort": "select",
        "%while.6 while": "mmr", "%copy-start copy-start": None}}


def test_nested_ops_count_once():
    events = [(None, 1.0, 2.0), ("mmr", 1.1, 1.2), ("mmr", 1.3, 1.4),  # a while and its body
              ("score", 2.0, 2.5), (None, 3.0, 3.1), ("select", 2.4, 2.45)]
    assert L.top_level(events) == [("mmr", 1.0, 2.0), ("score", 2.0, 2.5), (None, 3.0, 3.1)]


def test_scope_seconds_clip_and_average():
    events = {"/device:TPU:0": [("score", 0.5, 1.5), ("select", 1.5, 2.0), (None, 2.0, 2.2)],
              "/device:TPU:1": [("score", 1.0, 1.4)]}
    got = L.scope_seconds(events, (1.0, 3.0))
    assert got == pytest.approx({"score": (0.5 + 0.4) / 2, "select": 0.25, "none": 0.1})


def test_read_scoped_profile_names_ops_by_scope_on_the_host_clock(tmp_path):
    tf_op, category = 3, 4

    def meta(i, name, *stats):
        return (4, _pb((1, i), (2, _pb((1, i), (2, name), *[(5, st) for st in stats]))))

    def event(i, start_us, dur_us):
        return _pb((1, i), (2, start_us * 1_000_000), (3, dur_us * 1_000_000))

    def line(name, *events):
        return (3, _pb((1, 1), (2, name), (3, 1000), *[(4, e) for e in events]))

    host = _pb((1, 1), (2, "/host:CPU"), meta(1, tracing.MARKER), line("annotations", event(1, 0, 1)))
    device = _pb(
        (1, 2), (2, "/device:TPU:0"),
        meta(1, "%fusion.1 = f32[8] fusion()", _pb((1, tf_op), (5, "jit(f)/score/dot:"))),
        meta(2, "%while.6 = s32[] while()"),
        meta(3, "%fusion.9 = f32[8] fusion()", _pb((1, tf_op), (5, "jit(f)/mmr/while/body:"))),
        meta(4, "%sort.2 = f32[8] sort()", _pb((1, category), (5, "sort"))),
        (5, _pb((1, tf_op), (2, _pb((1, tf_op), (2, "tf_op"))))),
        (5, _pb((1, category), (2, _pb((1, category), (2, "hlo_category"))))),
        line("XLA Ops", event(1, 0, 2), event(2, 3, 5), event(3, 4, 1), event(4, 10, 1)))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb((1, host), (1, device)))
    got = L.read_scoped_profile(str(path), marker_pc_ns=5 * S)
    # the marker's trace time (1000 ns) is 5 s on the host clock; the
    # while's body op folds into the while, which takes the body's scope
    assert [s for s, _, _ in got["/device:TPU:0"]] == ["score", "mmr", "select"]
    starts = [a for _, a, _ in got["/device:TPU:0"]]
    assert starts == pytest.approx([5.0, 5.0 + 3e-6, 5.0 + 10e-6])
