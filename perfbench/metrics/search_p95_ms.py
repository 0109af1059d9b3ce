"""search_p95_ms: 95th percentile of every search sent in the window (from
its due time in an open loop), on the host clock."""


def read(run):
    return run.summary["p95_ms"] if run.surface == "search" else None
