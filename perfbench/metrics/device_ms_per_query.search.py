"""device_ms_per_query.search: device busy time in the traced slice per
search answered there (layer: device pass)."""

from perfbench.lib.layers import device_ms_per_request


def read(run):
    return device_ms_per_request(run, "search")
