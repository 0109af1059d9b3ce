"""phase1_ms_per_stmt.sql: `sql.phase1` time (the pre-filter SELECT) per
statement, 0 for those without one (layer: Phase-1 SQL)."""

from perfbench.lib.layer_spans import span_ms_per_request


def read(run):
    return span_ms_per_request(run, "sql", ("sql.phase1",))
