"""Benchmark harness: one function per paper table + beyond-paper engine
benches. Prints ``name,us_per_call,derived`` CSV.

    PYTHONPATH=src python -m benchmarks.run [table2 table3 ...]
    FLEX_BENCH_SCALE=0.02 ... (smoke scale)
"""

from __future__ import annotations

import sys
import time


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (behavioral, case_study, kernel_bench, latency,
                            pem_snapshot, scaling)

    suites = {
        "table2": latency.run,
        # table3 (SQL pre-filtering) folded into the snapshot's gated
        # prefilter_backends scenario; the standalone suite runs it alone
        "table3": pem_snapshot.run_prefilter,
        "table4": scaling.run,
        "table5+6": behavioral.run,
        "table7": case_study.run,
        "kernel": kernel_bench.run,
        "pem": pem_snapshot.run,
    }
    want = sys.argv[1:] or list(suites)
    print("name,us_per_call,derived")
    for name in want:
        key = name if name in suites else {"table5": "table5+6", "table6": "table5+6"}.get(name)
        if key is None:
            raise SystemExit(f"unknown suite {name}; known: {list(suites)}")
        t0 = time.time()
        suites[key]()
        print(f"# suite {key} done in {time.time()-t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
