"""select_ms_per_query.search: device op time under the `select` stage
scope (top_k) per search (layer: selection)."""

from perfbench.lib.layer_spans import scope_ms_per_request


def read(run):
    return scope_ms_per_request(run, "search", "select")
