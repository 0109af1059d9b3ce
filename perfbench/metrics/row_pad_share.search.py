"""row_pad_share.search: device rows that hold no corpus row, as a share
of the corpus rows, ``(row_grid - rows_placed) / rows_placed`` from the
backend's device cache counters (layer: device placement).  It is fixed
by the row count and the row grid's rule (0.008038 at 8,841,823 rows), so
it does not vary between runs: it shows that placement holds the grid's
rows and no more.  None where the backend does not count them."""


def read(run):
    if run.surface != "search":
        return None
    dev = (run.counters or {}).get("device_cache") or {}
    placed, grid = dev.get("rows_placed"), dev.get("row_grid")
    if not placed or grid is None:
        return None
    return (grid - placed) / placed
