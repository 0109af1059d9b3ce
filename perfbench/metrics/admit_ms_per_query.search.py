"""admit_ms_per_query.search: `engine.admit` time (slot, parse + embed,
validate, plan filter on the caller's thread) per search (layer: admission)."""

from perfbench.lib.layer_spans import span_ms_per_request


def read(run):
    return span_ms_per_request(run, "search", ("engine.admit",))
