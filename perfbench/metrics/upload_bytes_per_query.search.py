"""upload_bytes_per_query.search: host-to-device bytes (`upload_bytes` on
`engine.device`) per search (layer: device stage (host side))."""

from perfbench.lib.layer_spans import upload_bytes_per_request


def read(run):
    return upload_bytes_per_request(run, "search")
