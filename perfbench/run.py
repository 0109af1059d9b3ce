#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

In order: build the cell's corpus from the seed, through the corpus module
the configuration names; bring up the served path the configuration names;
warm every batch shape the cell's traffic forms
(the persistent compilation cache lives in ``.jax_cache`` inside this
checkout); drive the traffic for ``--seconds``; check a seeded sample of
the window's answers against the plain reference; print one JSON result
as the last line of standard output.  ``--trace 1`` traces a few seconds
of the window, records the program's spans over the window
(``repro.core.spans``), and reports the per-layer metrics instead of the
end-to-end ones.

Exits non-zero with no result line when JAX finds no TPU, or fewer chips
than the cell asks for.  Everything runs in this one process: a chip
belongs to one process at a time.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Dict, List, Optional, Sequence  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

#: the persistent compilation cache: a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"

import jax  # noqa: E402

from repro.core.spans import RECORDER, Span  # noqa: E402

from perfbench.lib import check, drive, layer_spans, measure, tracing, traffic as traffic_mod  # noqa: E402
from perfbench.lib.bench import Benchmark, load_module, read_metrics  # noqa: E402

TRACE_START, TRACE_SECONDS = 0.3, 4.0


def enable_compile_cache() -> None:
    """Keep every compiled program, however small, in ``CACHE_DIR``."""
    CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)  # no LRU eviction


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run: ``counters`` is the system's
    ``counters()`` after the window; a traced run adds the reduced
    ``trace``, the program's ``spans`` recorded over the window and the
    device seconds per stage scope in the traced slice, ``scope_seconds``."""

    workload: str
    surface: str
    cfg: dict
    traffic: dict
    setup_s: float
    summary: Dict[str, float]
    records: List[dict]
    device: Dict[str, object]
    counters: Dict[str, object]
    trace: Optional[tracing.Trace] = None
    spans: Optional[List[Span]] = None
    scope_seconds: Optional[Dict[str, float]] = None


def log(**fields) -> None:
    print("perfbench " + json.dumps(fields, default=str), flush=True)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             device_check: Callable[[int], Dict[str, object]] = measure.platform_or_exit,
             config_overrides: Optional[dict] = None,
             bench: Optional[Benchmark] = None) -> dict:
    """One run; returns the result object (the last line's JSON)."""
    bench = bench or Benchmark(ROOT)
    cell = bench.workload(workload)
    device = device_check(int(cell["chips"]))
    cfg = dict(bench.config(cell["config"]), **(config_overrides or {}))
    traffic = bench.traffic(cell["traffic"])
    limits = bench.limits(workload)
    system_mod = load_module(bench.system_path(cfg["system"]), f"perfbench_system_{cfg['system']}")
    corpus_mod = bench.corpus(cfg)
    generator, requests = bench.generator(traffic), bench.requests(traffic)

    t_build = time.perf_counter()
    embedding = corpus_mod.embedding(cfg)
    corpus = corpus_mod.generate(cfg, seed, embedding)
    t_gen = time.perf_counter() - t_build
    clock = measure.CompileClock()
    with clock.watch():
        system = system_mod.System(cfg, corpus, embedding)
        t_up = time.perf_counter() - t_build - t_gen
        passes = requests.warm(system, traffic, traffic_mod.warm_requests(traffic, seed, requests))
        # what set-up made stays for the run: collect once and freeze it, as
        # a server does after warm-up, so the collector's passes inside the
        # window scan only what the window makes
        gc.collect()
        gc.freeze()
    setup_s = time.perf_counter() - _T_START
    log(event="setup", workload=workload, seed=seed, rows=corpus.n, corpus_s=round(t_gen, 3),
        bring_up_s=round(t_up, 3), warm_passes=passes, setup_s=round(setup_s, 3),
        setup_compiles=clock.counts(), router_threshold=system.counters().get("router_threshold"))

    trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if trace else None
    tracer = (tracing.Tracer(trace_dir, seconds * TRACE_START, min(TRACE_SECONDS, seconds * 0.4))
              if trace else None)
    inside = measure.CompileClock()
    reduced = scope_seconds = None
    if trace:
        RECORDER.drain()  # a reader sees the window's spans and no others
        RECORDER.on = True
    try:
        try:
            with inside.watch():
                records, t0, close = generator.drive_window(traffic, seed, seconds, requests,
                                                            system, tracer.begin if tracer else None)
        finally:
            RECORDER.on = False
        spans = RECORDER.drain() if trace else None
        if tracer:
            reduced = tracer.join()
            scope_seconds = layer_spans.scope_seconds(
                layer_spans.read_scoped_profile(tracer.path, tracer.marker_pc_ns), reduced.window)
            log(event="trace_layout", planes=tracing.profile_layout(tracer.path))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    gc.unfreeze()
    device = dict(device, memory_peak_bytes=measure.memory_peak_bytes(int(cell["chips"])))
    summary = drive.summarize(records, t0, close)
    counters = system.counters()
    system.close()
    log(event="window", workload=workload, seconds=seconds, window_compiles=inside.counts(),
        summary=summary, by_kind=drive.by_kind(records), counters=counters)

    # the reference: a seeded sample of the window's answers, after the close;
    # a request kind whose answers the reference cannot score (a write)
    # brings its own comparison
    t_check = time.perf_counter()
    sampled = drive.sample(records, int(traffic["check_sample"]), seed)
    numbers = getattr(requests, "compare_all", check.compare_all)(
        corpus_mod.reference(corpus, embedding, "f64"), [r["spec"] for r in sampled],
        [r["answer"] if r["error"] is None else None for r in sampled])
    correct = check.verdict(numbers, limits) and bool(sampled)
    log(event="check", sampled=len(sampled), check_s=round(time.perf_counter() - t_check, 3))

    run = Run(workload, requests.SURFACE, cfg, traffic, setup_s, summary, records, device,
              counters, reduced, spans, scope_seconds)
    kind = "per_layer" if trace else "end_to_end"
    result = {"correct": correct, "attempted": summary["attempted"],
              "failed": summary["failed"],
              "metrics": read_metrics(bench, bench.metrics_for(workload, kind), run),
              "device": device}
    if reduced is not None:
        result["device"] = dict(device, busy_s=reduced.busy_s, window_s=reduced.window_s)
        idle = layer_spans.idle_by_span(reduced, records, spans)
        result["breakdown"] = {"device_ops": tracing.top_ops(reduced),
                               "idle_gaps": tracing.name_gaps(reduced, records),
                               "idle_by_span": sorted(([k, v] for k, v in idle.items()),
                                                      key=lambda kv: -kv[1])[:10]}
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]} for k in check.NUMBERS}
    return result


def print_result(result: dict) -> None:
    for k, v in result["checks"].items():
        print(f"check {k} = {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = parse_args(argv)
    enable_compile_cache()
    print_result(run_cell(args.workload, args.seed, args.seconds, bool(args.trace)))


if __name__ == "__main__":
    main()
