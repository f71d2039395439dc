"""Paper §5.3.2: perShardTopK — merge-payload reduction vs recall cost.

The collective-volume claim: per-shard results shrink from topK to
perShardTopK, cutting broker network bytes by topK/perShardTopK; we measure
the actual recall cost on data (the paper only states the formula)."""

from __future__ import annotations


from benchmarks.common import emit, ground_truth, sift_like_corpus, time_call
from repro.core import LannsConfig, LannsIndex, per_shard_topk, recall_at_k


def run(n=16_000, d=64, n_queries=300, topk=100):
    corpus, queries = sift_like_corpus(n, d, n_queries, seed=21)
    td, ti = ground_truth(corpus, queries, topk)

    for S in (4, 8, 16):
        for conf in (0.9, 0.95, 0.99):
            pstk = per_shard_topk(topk, S, conf)
            cfg = LannsConfig(
                num_shards=S, num_segments=1, segmenter="rs", engine="scan",
                topk_confidence=conf,
            )
            idx = LannsIndex(cfg).build(corpus)
            tq, (dd, ii) = time_call(idx.query, queries, topk, repeats=2)
            r = recall_at_k(ii, ti, topk)
            payload_ratio = topk / pstk
            emit(
                f"pershard_topk.S{S}.p{conf}",
                1e6 * tq / len(queries),
                f"pstk={pstk};R@100={r:.4f};merge_bytes_saved={payload_ratio:.1f}x",
            )
        # reference: no trimming
        cfg = LannsConfig(
            num_shards=S, num_segments=1, segmenter="rs", engine="scan",
            topk_confidence=0.999999,
        )
        idx = LannsIndex(cfg).build(corpus)
        tq, (dd, ii) = time_call(idx.query, queries, topk, repeats=2)
        emit(
            f"pershard_topk.S{S}.full",
            1e6 * tq / len(queries),
            f"pstk=100;R@100={recall_at_k(ii, ti, topk):.4f};merge_bytes_saved=1.0x",
        )


if __name__ == "__main__":
    from repro.common.utils import enable_compile_cache

    enable_compile_cache()
    run()
