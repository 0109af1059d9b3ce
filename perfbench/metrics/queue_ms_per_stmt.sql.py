"""queue_ms_per_stmt.sql: `engine.queue` time of the engine requests the
statements own, per statement (layer: scheduler)."""

from perfbench.lib.layer_spans import statement_queue_ms


def read(run):
    return statement_queue_ms(run)
