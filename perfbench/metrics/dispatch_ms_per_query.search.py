"""dispatch_ms_per_query.search: `engine.device` minus the device's busy
time inside it, per search (layer: device stage (host side))."""

from perfbench.lib.layer_spans import dispatch_ms_per_request


def read(run):
    return dispatch_ms_per_request(run, "search")
