"""Paper Tables 1 & 4: recall of (n, m)-partitioned LANNS vs monolithic HNSW.

Reduced-scale protocol (SIFT64-20k): same methods, same (1,8)/(2,4)
partitionings, same alpha=0.15, topK=100, R@{1,5,10,15,50,100}.

``--quantized`` runs the two-stage q8 acceptance protocol instead: the fp32
jnp scan path vs the quantized scan (int8 candidates + exact re-rank) at
B=1024/k=100 — QPS, recall@k against ground truth AND relative to fp32, and
the resident bytes-per-vector of each corpus, so the memory win is a
tracked number next to the throughput win.
"""

from __future__ import annotations

import argparse

from benchmarks.common import (
    bench_payload,
    emit,
    ground_truth,
    quantized_compare,
    sift_like_corpus,
    time_call,
    write_bench_json,
)
from repro.core import (
    HNSWConfig,
    HNSWIndex,
    LannsConfig,
    LannsIndex,
    recall_at_k,
    recall_table,
)

KS = (1, 5, 10, 15, 50, 100)


def run(n=20_000, d=64, n_queries=300, topk=100, engine="scan",
        out="BENCH_recall_table1.json"):
    corpus, queries = sift_like_corpus(n, d, n_queries)
    td, ti = ground_truth(corpus, queries, topk)
    results = {}

    # monolithic HNSW baseline (paper's single-machine row)
    hnsw = HNSWIndex(HNSWConfig(M=12, ef_construction=80, ef_search=120), d)
    t_build, _ = time_call(lambda: hnsw.add_batch(corpus), repeats=1)
    t_query, (dh, ih) = time_call(hnsw.search_np, queries, topk, repeats=1)
    results["HNSW"] = recall_table(ih, ti, KS)
    emit(
        "table1_recall.HNSW",
        1e6 * t_query / len(queries),
        ";".join(f"R@{k}={v:.4f}" for k, v in results["HNSW"].items())
        + f";build_s={t_build:.1f}",
    )

    for seg, (S, m) in [
        (s, p) for s in ("rs", "rh", "apd") for p in ((1, 8), (2, 4))
    ]:
        cfg = LannsConfig(
            num_shards=S, num_segments=m, segmenter=seg, alpha=0.15,
            engine=engine, hnsw_m=12, ef_construction=80, ef_search=120,
            topk_confidence=0.95,
        )
        idx = LannsIndex(cfg)
        t_build, _ = time_call(lambda: idx.build(corpus), repeats=1)
        t_query, (dl, il) = time_call(idx.query, queries, topk, repeats=1)
        name = f"{seg.upper()}({S},{m})"
        results[name] = recall_table(il, ti, KS)
        emit(
            f"table1_recall.{name}",
            1e6 * t_query / len(queries),
            ";".join(f"R@{k}={v:.4f}" for k, v in results[name].items())
            + f";build_s={t_build:.1f}",
        )
    payload = bench_payload(
        # distinct bench name: the committed baseline entry for "recall"
        # gates the quantized protocol; the table-1 sweep is reported only.
        "recall_table1",
        config=dict(n=n, d=d, n_queries=n_queries, topk=topk, engine=engine,  # noqa: C408 -- kwargs mirror the CLI flag names
                    mode="table1"),
        metrics={
            f"recall_at_10_{name}": table[10]
            for name, table in results.items()
        },
        rows=[{"method": name, **{f"R@{k}": v for k, v in table.items()}}
              for name, table in results.items()],
    )
    write_bench_json(out, payload)
    return results


def run_quantized(n=20_000, d=64, batch=1024, topk=100, smoke=False,
                  engine="scan", out="BENCH_recall.json"):
    """q8 vs fp32 on one engine: QPS, recall, resident bytes-per-vector.

    The acceptance protocol rides the shared harness in benchmarks/common.py
    (same one the bench_online_qps quantized legs use); this entry point
    adds the ground-truth recall columns.  ``engine='hnsw'`` benches the
    quantized beam (+ exact re-rank) against the fp32 flat beam — the
    ISSUE-5 acceptance bound is recall@100 within 0.01 of fp32 (smaller n:
    the per-partition HNSW builds are the sequential numpy loop).
    """
    if engine == "hnsw" and n > 12_000:
        n = 12_000
    if smoke:
        n, batch, topk = (2000, 256, 20) if engine == "hnsw" \
            else (3000, 256, 20)
    corpus, queries = sift_like_corpus(n, d, max(batch, 1024), seed=31)
    td, ti = ground_truth(corpus, queries, topk)
    stats = quantized_compare(
        corpus, queries, topk, batch, prefix="quantized", engine=engine
    )
    r_fp = recall_at_k(stats["ids_fp32"], ti[: len(stats["ids_fp32"])], topk)
    r_q8 = recall_at_k(stats["ids_q8"], ti[: len(stats["ids_q8"])], topk)
    emit(
        f"quantized.truth_recall_{engine}_b{batch}",
        0.0,
        f"R@{topk}_fp32={r_fp:.4f};R@{topk}_q8={r_q8:.4f}",
    )
    stats.update(recall_fp32=r_fp, recall_q8=r_q8)
    bench = "recall" if engine == "scan" else "recall_q8_hnsw"
    payload = bench_payload(
        bench,
        config=dict(n=n, d=d, batch=batch, topk=topk, mode="quantized",  # noqa: C408 -- kwargs mirror the CLI flag names
                    engine=engine),
        metrics={
            f"qps_{engine}_fp32": stats["qps_fp32"],
            f"qps_{engine}_q8": stats["qps_q8"],
            "q8_rel_recall": stats["rel_recall"],
            "recall_fp32": r_fp,
            "recall_q8": r_q8,
            "q8_bytes_per_vec": stats["bytes_per_vec_q8"],
        },
        smoke=smoke,
    )
    write_bench_json(out, payload)
    return stats


if __name__ == "__main__":
    from repro.common.utils import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quantized", action="store_true",
                    help="q8 vs fp32 acceptance protocol (see --engine)")
    ap.add_argument("--engine", default="scan", choices=("scan", "hnsw"),
                    help="engine for the --quantized protocol: 'scan' "
                         "(two-stage int8 scan) or 'hnsw' (quantized beam "
                         "+ exact re-rank)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny corpus (CI wiring check)")
    ap.add_argument("--out", default=None,
                    help="output JSON path (defaults: BENCH_recall.json for "
                         "--quantized, BENCH_recall_q8_hnsw.json for "
                         "--quantized --engine hnsw, BENCH_recall_table1."
                         "json otherwise — distinct so the legs never "
                         "clobber each other)")
    args = ap.parse_args()
    if args.quantized:
        default_out = (
            "BENCH_recall.json" if args.engine == "scan"
            else "BENCH_recall_q8_hnsw.json"
        )
        run_quantized(smoke=args.smoke, engine=args.engine,
                      out=args.out or default_out)
    else:
        run(out=args.out or "BENCH_recall_table1.json")
