"""sql_p95_ms: 95th percentile of every statement sent in the window."""


def read(run):
    return run.summary["p95_ms"] if run.surface == "sql" else None
