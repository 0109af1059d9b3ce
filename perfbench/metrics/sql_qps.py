"""sql_qps: statements answered within the window over its length."""


def read(run):
    return run.summary["rate"] if run.surface == "sql" else None
