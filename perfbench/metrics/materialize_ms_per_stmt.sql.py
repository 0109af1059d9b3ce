"""materialize_ms_per_stmt.sql: `sql.materialize` (min-max, temp table,
insert, snippet UPDATE) plus `sql.select` per statement (layer: SQL result)."""

from perfbench.lib.layer_spans import span_ms_per_request


def read(run):
    return span_ms_per_request(run, "sql", ("sql.materialize", "sql.select"))
