"""host_ms_per_stmt.sql: statement span minus device busy time inside it
(layer: SQL surface — materializer, Phase-1 SQLite, FTS5)."""

from perfbench.lib.layers import host_ms_per_request


def read(run):
    return host_ms_per_request(run, "sql")
