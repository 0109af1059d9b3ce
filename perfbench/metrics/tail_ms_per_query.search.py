"""tail_ms_per_query.search: `engine.tail` time (finish, id resolution,
delivery) per search (layer: host tail)."""

from perfbench.lib.layer_spans import span_ms_per_request


def read(run):
    return span_ms_per_request(run, "search", ("engine.tail",))
