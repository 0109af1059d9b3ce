#!/usr/bin/env python3
"""The control for ``correct``: the reference in bfloat16 in the program's place.

    python3 perfbench/control.py --workload h1m_search_single --seeds 11 12 13

For each seed it builds the cell's corpus through the configuration's
corpus module, draws the window's requests as a run would (through the
traffic's generator and request modules), samples them as a run's check
does, answers them with the module's reference computed from bfloat16 rows
and queries (float32 accumulation), and holds those answers to the float64
reference with the cell's comparison.  A
number the control reads is an upper reading for that number's limit;
the benchmark's own runs never run this.  It needs no chip.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Dict, Optional

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perfbench.lib import check, drive  # noqa: E402
from perfbench.lib.bench import Benchmark  # noqa: E402
from perfbench.lib.reference import answers  # noqa: E402


def control_numbers(workload: str, seed: int, seconds: float = 20.0,
                    config_overrides: Optional[dict] = None,
                    bench: Optional[Benchmark] = None) -> Dict[str, float]:
    bench = bench or Benchmark(ROOT)
    cell = bench.workload(workload)
    cfg = dict(bench.config(cell["config"]), **(config_overrides or {}))
    traffic = bench.traffic(cell["traffic"])
    corpus_mod = bench.corpus(cfg)
    emb = corpus_mod.embedding(cfg)
    corpus = corpus_mod.generate(cfg, seed, emb)
    specs = bench.generator(traffic).window_requests(traffic, seed, seconds,
                                                     bench.requests(traffic))
    records = [{"spec": s} for s in specs]
    specs = [r["spec"] for r in drive.sample(records, int(traffic["check_sample"]), seed)]
    got = answers(corpus_mod.reference(corpus, emb, "bf16"), specs)
    return check.compare_all(corpus_mod.reference(corpus, emb, "f64"), specs,
                             [got[j] for j in range(len(specs))])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    bench = Benchmark(ROOT)
    limits = bench.limits(args.workload)
    for seed in args.seeds:
        numbers = control_numbers(args.workload, seed, bench=bench)
        print("control " + json.dumps({"workload": args.workload, "seed": seed,
                                       "numbers": numbers, "limits": limits,
                                       "correct": check.verdict(numbers, limits)}), flush=True)


if __name__ == "__main__":
    main()
