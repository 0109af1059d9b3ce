"""merge_ms_per_pass.search: device time of the cross-chip collectives per
device pass (layer: cross-chip merge).

The collectives are the trace's ops whose opcode is a collective; the
sharded graph's only ones are the all-gathers of the shards' candidates
and pool rows in ``dist/pem_sharded.py``'s union merge.  The union
``top_k`` that follows them is not counted here.  A pass is one
``engine.device`` span that ends in the traced slice.  The time is per
chip, averaged over the chips (the trace's op times are averaged so; the
chips run the same collectives).  None off the sharded path, where no pass
has a collective.
"""

from perfbench.lib.passes import device_passes

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def opcode(op: str) -> str:
    """``all-gather`` from ``%all-gather.15 all-gather s32[128,2048]``."""
    parts = op.split(" ")
    return parts[1] if len(parts) > 1 else ""


def read(run):
    if run.trace is None or run.spans is None or run.surface != "search":
        return None
    n = device_passes(run)
    seconds = sum(s for op, s in run.trace.op_seconds.items()
                  if opcode(op).startswith(COLLECTIVES))
    if not n or seconds <= 0:
        return None
    return seconds / n * 1e3
