"""host_ms_per_query.search: search span minus device busy time inside it
(layer: host path — engine, grammar, finish)."""

from perfbench.lib.layers import host_ms_per_request


def read(run):
    return host_ms_per_request(run, "search")
