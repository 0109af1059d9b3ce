"""device_ms_per_stmt.sql: device busy time in the traced slice per
statement answered there (layer: device pass)."""

from perfbench.lib.layers import device_ms_per_request


def read(run):
    return device_ms_per_request(run, "sql")
