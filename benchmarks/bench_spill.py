"""Paper Table 7: physical vs virtual spill — recall/QPS/memory vs
(segments, spill%).  APD segmenter, single shard, scan engine (the paper's
Groups benchmark uses FAISS-HNSW inside segments; the engine choice doesn't
change the spill trade-off being measured)."""

from __future__ import annotations


from benchmarks.common import emit, ground_truth, sift_like_corpus, time_call
from repro.core import LannsConfig, LannsIndex, recall_at_k


def run(n=12_000, d=64, n_queries=300, topk=100):
    corpus, queries = sift_like_corpus(n, d, n_queries, seed=7)
    td, ti = ground_truth(corpus, queries, topk)

    # reference row: 1 segment, no spill
    cfg = LannsConfig(num_shards=1, num_segments=1, segmenter="rs", engine="scan")
    idx = LannsIndex(cfg).build(corpus)
    tq, (dd, ii) = time_call(idx.query, queries, 15, repeats=2)
    emit(
        "table7_spill.seg1.none",
        1e6 * tq / len(queries),
        f"R@15={recall_at_k(ii, ti, 15):.4f};qps={len(queries)/tq:.0f};mem=1.00x",
    )

    for m in (4, 8, 16):
        for alpha_pct in (5, 10, 15):  # alpha: spill band per side
            alpha = alpha_pct / 100.0
            for spill in ("physical", "virtual"):
                cfg = LannsConfig(
                    num_shards=1, num_segments=m, segmenter="apd",
                    alpha=alpha, spill=spill, engine="scan",
                )
                idx = LannsIndex(cfg).build(corpus)
                tq, (dd, ii) = time_call(idx.query, queries, 15, repeats=2)
                r = recall_at_k(ii, ti, 15)
                dup = idx.build_stats["duplication_factor"]
                emit(
                    f"table7_spill.seg{m}.a{alpha_pct}.{spill}",
                    1e6 * tq / len(queries),
                    f"R@15={r:.4f};qps={len(queries)/tq:.0f};mem={dup:.2f}x",
                )


if __name__ == "__main__":
    from repro.common.utils import enable_compile_cache

    enable_compile_cache()
    run()
