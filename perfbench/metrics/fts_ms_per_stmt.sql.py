"""fts_ms_per_stmt.sql: `sql.fts` time (FTS5 BM25 queries) per statement
(layer: FTS5)."""

from perfbench.lib.layer_spans import span_ms_per_request


def read(run):
    return span_ms_per_request(run, "sql", ("sql.fts",))
