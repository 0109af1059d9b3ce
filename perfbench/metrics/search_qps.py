"""search_qps: searches answered within the window over its length."""


def read(run):
    return run.summary["rate"] if run.surface == "search" else None
