"""shard_scan_roofline.search (%): the least time of one float32 read of
the corpus rows a chip holds (``rows / chips x dim x 4`` bytes at the
chip's HBM bandwidth, ``tracing.PEAKS``) per device pass, over that chip's
device time under the ``score`` stage scope (layer: scoring kernel).

A pass is one ``engine.device`` span that ends in the traced slice; the
scope's time is per chip, averaged over the chips.  The read bounds the
scan: its matmul is ``2 x rows / chips x dim x B`` FLOP, 109 GFLOP a chip
at 8,841,823 rows, d 768 and B 32, which at HIGHEST (six bf16 passes) is
3.3 ms at the 197 TFLOP/s bf16 peak against 8.4 ms for the read at
819 GB/s.  A pass with a suppress direction reads the rows twice (two
matmuls) and counts once, so it reads at most 50% there.
"""

from perfbench.lib import tracing
from perfbench.lib.passes import device_passes


def read(run):
    if run.trace is None or run.spans is None or run.surface != "search":
        return None
    score = (run.scope_seconds or {}).get("score")
    n = device_passes(run)
    if not n or not score:
        return None
    bw = tracing.peaks(str(run.device["kind"]))["hbm_bytes_per_s"]
    rows = int(run.cfg["rows"]) / int(run.device["count"])
    least = n * rows * int(run.cfg["dim"]) * 4 / bw
    return least / score * 100.0
