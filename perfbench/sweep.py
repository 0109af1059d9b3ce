#!/usr/bin/env python3
"""Find the knee of an open-loop mix: one corpus, one warm-up, then the
mix at each offered rate in turn.

    python3 perfbench/sweep.py --config agent_history_1m --traffic search_open \
        --seed 7 --seconds 8 --rates 200 300 400 500 600

For each rate it prints the completed rate, latency percentiles, how late
the generator ran and the backlog: requests still unanswered when the
window closed, and the p95 of the window's last fifth against its first.
The knee is the highest rate whose backlog does not grow.  The benchmark
itself never sweeps: an open-loop cell's rate is a number in its traffic file.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run as R  # noqa: E402

import numpy as np  # noqa: E402

from perfbench.lib import drive, measure, traffic as traffic_mod  # noqa: E402
from perfbench.lib.bench import Benchmark, load_module  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    R.enable_compile_cache()
    bench = Benchmark(R.ROOT)
    device = measure.platform_or_exit(1)
    cfg = bench.config(args.config)
    traffic = bench.traffic(args.traffic)
    corpus_mod = bench.corpus(cfg)
    emb = corpus_mod.embedding(cfg)
    system = load_module(bench.system_path(cfg["system"]), "sweep_system").System(
        cfg, corpus_mod.generate(cfg, args.seed, emb), emb)
    generator, requests = bench.generator(traffic), bench.requests(traffic)
    requests.warm(system, traffic, traffic_mod.warm_requests(traffic, args.seed, requests))
    try:
        for i, rate in enumerate(args.rates):
            t = dict(traffic, rate=rate)
            clock = measure.CompileClock()
            with clock.watch():
                recs, t0, close = generator.drive_window(t, args.seed + i, args.seconds,
                                                         requests, system)
            s = drive.summarize(recs, t0, close)
            fifth = args.seconds / 5
            lat = [(r["due"] - t0, (r["end"] - r["due"]) * 1e3) for r in recs
                   if r["end"] is not None and r["error"] is None]
            head = [ms for d, ms in lat if d < fifth]
            tail = [ms for d, ms in lat if d >= args.seconds - fifth]
            print("sweep " + json.dumps({
                "rate": rate, "device": device["kind"], "completed_rate": s["rate"],
                "p50_ms": s["p50_ms"], "p95_ms": s["p95_ms"], "late_p95_ms": s["late_p95_ms"],
                "unanswered_at_close": sum(1 for r in recs if r["end"] is None or r["end"] > close),
                "p95_first_fifth_ms": float(np.percentile(head, 95)) if head else None,
                "p95_last_fifth_ms": float(np.percentile(tail, 95)) if tail else None,
                "failed": s["failed"], "compiles": clock.counts()["lowerings"]}), flush=True)
    finally:
        system.close()


if __name__ == "__main__":
    main()
