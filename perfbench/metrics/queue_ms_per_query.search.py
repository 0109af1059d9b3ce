"""queue_ms_per_query.search: `engine.queue` time (admitted to the start
of its batch's device stage) per search (layer: scheduler)."""

from perfbench.lib.layer_spans import span_ms_per_request


def read(run):
    return span_ms_per_request(run, "search", ("engine.queue",))
