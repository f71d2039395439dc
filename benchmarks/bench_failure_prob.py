"""Paper Figure 4: analytic failure-probability curve vs tree depth, PLUS an
empirical check the paper doesn't do: measured miss-rate of the exact nearest
neighbor under RH segmentation at each depth."""

from __future__ import annotations

import numpy as np

from benchmarks.common import emit, ground_truth, sift_like_corpus
from repro.core import SegmenterConfig, make_segmenter
from repro.core.segmenter import failure_probability


def run(n=10_000, d=32, n_queries=400):
    # analytic curve at the paper's n=10k
    levels = np.arange(1, 9)
    for alpha in (0.05, 0.1, 0.15, 0.2):
        p = failure_probability(levels, alpha=alpha, n=n)
        emit(
            f"fig4_analytic.alpha{alpha}",
            0.0,
            ";".join(f"L{l}={v:.2e}" for l, v in zip(levels, p)),
        )

    # empirical: fraction of queries whose true 1-NN lands in a segment the
    # query was NOT routed to (upper-bounds the R@1 drop from segmentation)
    corpus, queries = sift_like_corpus(n, d, n_queries, seed=11)
    td, ti = ground_truth(corpus, queries, 1)
    for L in (1, 2, 3):
        seg = make_segmenter(
            SegmenterConfig(kind="rh", num_segments=2**L, alpha=0.15, seed=3)
        ).fit(corpus)
        pmask = seg.route_points(corpus)
        qmask = seg.route_queries(queries)
        misses = 0
        for qi in range(n_queries):
            nn_seg = pmask[ti[qi, 0]]
            if not (qmask[qi] & nn_seg).any():
                misses += 1
        emit(
            f"fig4_empirical.rh.L{L}",
            0.0,
            f"miss_rate={misses / n_queries:.4f}",
        )


if __name__ == "__main__":
    from repro.common.utils import enable_compile_cache

    enable_compile_cache()
    run()
