"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks.common.emit).

  bench_recall                Table 1/4  recall vs HNSW per segmenter/partitioning
  bench_build_query_scaling   Table 2/3/5/6  build+query time vs executors
  bench_spill                 Table 7  physical vs virtual spill
  bench_failure_prob          Figure 4 analytic + empirical miss rates
  bench_pershard_topk         §5.3.2  merge-payload reduction vs recall
  bench_online_qps            §7/Table 8  single-node serving QPS/latency
  bench_kernels               fused distance+top-k traffic model
  roofline                    §Roofline terms from dry-run artifacts
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None, help="comma-separated bench names")
    p.add_argument("--fast", action="store_true", help="reduced sizes")
    args = p.parse_args(argv)

    from benchmarks import (
        bench_build_query_scaling,
        bench_failure_prob,
        bench_kernels,
        bench_online_qps,
        bench_pershard_topk,
        bench_recall,
        bench_spill,
        roofline,
    )

    suites = {
        "recall": lambda: bench_recall.run(
            n=8000 if args.fast else 20_000,
            n_queries=100 if args.fast else 300,
        ),
        "build_query_scaling": lambda: bench_build_query_scaling.run(
            n=6000 if args.fast else 20_000,
            n_queries=100 if args.fast else 200,
        ),
        "spill": lambda: bench_spill.run(
            n=6000 if args.fast else 12_000,
            n_queries=100 if args.fast else 300,
        ),
        "failure_prob": lambda: bench_failure_prob.run(
            n=4000 if args.fast else 10_000,
            n_queries=200 if args.fast else 400,
        ),
        "pershard_topk": lambda: bench_pershard_topk.run(
            n=6000 if args.fast else 16_000,
            n_queries=100 if args.fast else 300,
        ),
        "online_qps": lambda: bench_online_qps.run(
            n=6000 if args.fast else 16_000,
            duration_s=1.0 if args.fast else 3.0,
            n_hnsw=4000 if args.fast else 12_000,
        ),
        "kernels": bench_kernels.run,
        "roofline": roofline.run,
    }
    only = set(args.only.split(",")) if args.only else None
    failures = 0
    print("name,us_per_call,derived")
    for name, fn in suites.items():
        if only and name not in only:
            continue
        t0 = time.time()
        print(f"# === {name} ===", flush=True)
        try:
            fn()
        except Exception:  # noqa: BLE001 — report and continue the suite
            failures += 1
            traceback.print_exc()
        print(f"# === {name} done in {time.time() - t0:.0f}s ===", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    from repro.common.utils import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
