"""Paper Tables 2/3 & 5/6: build/query time vs number of executors — measured.

Two legs, both *measured wall time* (the seed simulated executor scaling as
an LPT makespan over per-partition times; this file replaced that with real
``ProcessPoolExecutor`` sweeps through ``LannsIndex.build(workers=E)``):

* **builder leg** — single-partition head-to-head of the seed's python-dict
  HNSW builder (``HNSWIndexLegacy``) vs the vectorized wavefront builder
  (``HNSWIndex.add_batch``): wall seconds, speedup, and recall@100 of both
  frozen graphs against brute-force ground truth (same frozen search path,
  so any gap is the *builder's* doing).
* **scaling leg** — segmented ``LannsIndex`` built with workers in
  {1, 2, 4, 8} vs one monolithic bulk HNSW over the full corpus, plus the
  query-side comparison (segmented fan-out vs monolithic search).

One-core caveat: this container exposes a single CPU core, so the worker
sweep is expected ~flat-to-slower here (process pools add pickling without
adding parallelism) — the numbers are still *measured*, and the sweep shape
becomes the paper's Tables 2/5 on any multi-core runner.  The
segmented-vs-monolithic speedup, by contrast, reproduces even on one core:
partition build cost is superlinear in n, so building S partitions of n/S
points beats one build of n points regardless of parallelism.

``--scale1m`` opts into a 1M x 64d segmented build (the paper-scale
offline-build demonstration; ~tens of minutes on one core — run it nightly
or by hand, never in the PR gate).

Every metric in BENCH_build.json is prefixed ``build_`` which
``check_regression.py`` treats as info-only: build wall time on shared
runners swings too much to gate merges, but drift stays visible in the
artifact.
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from benchmarks.common import (
    bench_payload,
    emit,
    ground_truth,
    sift_like_corpus,
    write_bench_json,
)
from repro.core import (
    HNSWConfig,
    HNSWIndex,
    HNSWIndexLegacy,
    LannsConfig,
    LannsIndex,
    recall_at_k,
)

WORKER_SWEEP = (1, 2, 4, 8)


def _wall(fn, *args, **kw):
    """One-shot wall time (builds are too slow to repeat; noise is quoted
    as such in the doc header rather than median-ed away)."""
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return time.perf_counter() - t0, out


def builder_leg(metrics, rows, *, n, d, n_queries, topk, ef):
    """Single-partition legacy-vs-bulk: the tentpole acceptance numbers."""
    corpus, queries = sift_like_corpus(n, d, n_queries=n_queries, seed=11)
    cfg = HNSWConfig(seed=7)

    t_bulk, bulk = _wall(lambda: HNSWIndex(cfg, d).add_batch(corpus))
    t_leg, leg = _wall(lambda: HNSWIndexLegacy(cfg, d).add_batch(corpus))
    speedup = t_leg / t_bulk

    gt = np.asarray(ground_truth(corpus, queries, k=topk)[1])
    recalls = {}
    for name, idx in (("bulk", bulk), ("legacy", leg)):
        # identical frozen-search path for both: recall isolates the builder
        _, ids = idx.freeze().search(queries, topk, ef=ef)
        recalls[name] = recall_at_k(np.asarray(ids), gt, topk)

    emit(
        f"table2_build.bulk.n{n}",
        1e6 * t_bulk / n,
        f"build_s={t_bulk:.1f};ms_per_point={1e3 * t_bulk / n:.3f};"
        f"recall@{topk}={recalls['bulk']:.4f}",
    )
    emit(
        f"table2_build.legacy.n{n}",
        1e6 * t_leg / n,
        f"build_s={t_leg:.1f};speedup={speedup:.2f}x;"
        f"recall@{topk}={recalls['legacy']:.4f}",
    )
    metrics.update(
        build_bulk_seconds=t_bulk,
        build_legacy_seconds=t_leg,
        build_bulk_speedup=speedup,
        build_recall_bulk=recalls["bulk"],
        build_recall_legacy=recalls["legacy"],
    )
    rows.append({
        "leg": "builder", "n": n, "d": d, "topk": topk, "ef": ef,
        "bulk_seconds": t_bulk, "legacy_seconds": t_leg, "speedup": speedup,
        "recall_bulk": recalls["bulk"], "recall_legacy": recalls["legacy"],
    })
    return corpus, queries


def scaling_leg(
    metrics, rows, corpus, queries, *,
    topk, segments, workers=WORKER_SWEEP, tag="",
):
    """Real process-pool executor sweep vs a monolithic bulk build."""
    n, d = corpus.shape
    base = dict(
        num_shards=1, num_segments=segments, segmenter="apd", alpha=0.15,
        engine="hnsw", hnsw_m=12, ef_construction=80,
        ef_search=max(topk, 120),
    )

    mono = HNSWIndex(HNSWConfig(M=12, ef_construction=80, seed=7), d)
    t_mono, _ = _wall(lambda: mono.add_batch(corpus))
    tq_mono, _ = _wall(mono.search_np, queries, topk)
    emit(
        f"table2_build{tag}.mono.e1", 1e6 * t_mono / n,
        f"build_s={t_mono:.1f}",
    )
    emit(
        f"table3_query{tag}.mono.e1", 1e6 * tq_mono / len(queries),
        f"ms/query={1e3 * tq_mono / len(queries):.2f}",
    )
    metrics[f"build{tag}_mono_seconds"] = t_mono

    t_seg1 = None
    for e in workers:
        idx = LannsIndex(LannsConfig(**base))
        t_build, _ = _wall(idx.build, corpus, workers=e)
        if t_seg1 is None:
            t_seg1 = t_build
            tq_seg, _ = _wall(idx.query, queries, topk)
            emit(
                f"table3_query{tag}.apd({segments}).e1",
                1e6 * tq_seg / len(queries),
                f"ms/query={1e3 * tq_seg / len(queries):.2f};"
                f"speedup={tq_mono / tq_seg:.2f}x",
            )
            metrics[f"build{tag}_query_seg_ms"] = 1e3 * tq_seg / len(queries)
            metrics[f"build{tag}_query_mono_ms"] = (
                1e3 * tq_mono / len(queries)
            )
        emit(
            f"table2_build{tag}.apd({segments}).e{e}",
            1e6 * t_build / n,
            f"build_s={t_build:.1f};speedup={t_mono / t_build:.2f}x;"
            f"vs_e1={t_seg1 / t_build:.2f}x",
        )
        metrics[f"build{tag}_seg_workers{e}_seconds"] = t_build
        rows.append({
            "leg": f"scaling{tag}", "n": n, "d": d, "segments": segments,
            "workers": e, "build_seconds": t_build,
            "mono_seconds": t_mono, "speedup_vs_mono": t_mono / t_build,
        })
    metrics[f"build{tag}_seg_speedup"] = t_mono / min(
        metrics[f"build{tag}_seg_workers{e}_seconds"] for e in workers
    )


def run(*, smoke=False, scale1m=False, out="BENCH_build.json"):
    metrics: dict = {}
    rows: list = []
    if smoke:
        corpus, queries = builder_leg(
            metrics, rows, n=4_000, d=32, n_queries=100, topk=100, ef=200,
        )
        scaling_leg(
            metrics, rows, corpus, queries,
            topk=100, segments=4, workers=(1, 2),
        )
    else:
        corpus, queries = builder_leg(
            metrics, rows, n=50_000, d=128, n_queries=500, topk=100, ef=200,
        )
        scaling_leg(
            metrics, rows, corpus, queries, topk=100, segments=8,
        )
    if scale1m:
        corpus, _ = sift_like_corpus(1_000_000, 64, n_queries=1, seed=3)
        queries = np.asarray(
            sift_like_corpus(4_000, 64, n_queries=200, seed=4)[1]
        )
        scaling_leg(
            metrics, rows, corpus, queries,
            topk=100, segments=16, workers=(8,), tag="_1m",
        )
    payload = bench_payload(
        "build",
        config={
            "smoke": smoke, "scale1m": scale1m,
            "worker_sweep": list(WORKER_SWEEP),
        },
        metrics=metrics,
        rows=rows,
        smoke=smoke,
    )
    write_bench_json(out, payload)
    return payload


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="measured build/query scaling (bulk builder + executors)"
    )
    ap.add_argument("--smoke", action="store_true",
                    help="small corpus + short worker sweep for CI")
    ap.add_argument("--scale1m", action="store_true",
                    help="add the 1M x 64d segmented build leg (slow)")
    ap.add_argument("--out", default="BENCH_build.json")
    args = ap.parse_args(argv)
    run(smoke=args.smoke, scale1m=args.scale1m, out=args.out)
    return 0


if __name__ == "__main__":
    from repro.common.utils import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
