"""Bulk-builder determinism and build-provenance tests.

The wavefront builder's contract (core/hnsw.py): for a fixed seed the frozen
graph is BIT-IDENTICAL regardless of the wavefront chunk size, of how the
points were split across add_batch calls, and of the process-pool worker
count — chunking and workers are throughput knobs only.  These tests pin
that contract, plus the amortized-growth behaviour of incremental adds and
the compact per-partition build-cost summary persisted in manifests.
"""

import json
import multiprocessing
import os

import numpy as np
import pytest

from repro.core import (
    HNSWConfig,
    HNSWIndex,
    HNSWIndexLegacy,
    LannsConfig,
    LannsIndex,
    brute_force_topk,
    recall_at_k,
)
from repro.core.lanns import (
    _build_one_partition,
    _merge_seconds_summary,
    _summarize_seconds,
)


def _corpus(n=800, d=16, seed=3):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, d)).astype(np.float32)


def _assert_frozen_identical(a, b):
    assert a.entry == b.entry
    np.testing.assert_array_equal(a.levels, b.levels)
    np.testing.assert_array_equal(a.adj0, b.adj0)
    np.testing.assert_array_equal(a.upper_adj, b.upper_adj)
    np.testing.assert_array_equal(a.vectors, b.vectors)
    if a.keys is not None or b.keys is not None:
        np.testing.assert_array_equal(a.keys, b.keys)


@pytest.fixture(scope="module")
def reference():
    data = _corpus()
    cfg = HNSWConfig(seed=7)
    frozen = HNSWIndex(cfg, data.shape[1]).add_batch(data).freeze()
    return data, cfg, frozen


@pytest.mark.parametrize("chunk", [1, 7, 64, 1000])
def test_chunk_invariance(reference, chunk):
    data, cfg, ref = reference
    frozen = (
        HNSWIndex(cfg, data.shape[1]).add_batch(data, chunk=chunk).freeze()
    )
    _assert_frozen_identical(frozen, ref)


@pytest.mark.parametrize("splits", [[800], [100, 700], [1, 399, 400],
                                    [37] * 21 + [23]])
def test_add_batch_split_invariance(reference, splits):
    """The RNG consumes one uniform per point in order, so splitting the
    ingest across calls cannot change level draws or insertion order."""
    data, cfg, ref = reference
    assert sum(splits) == len(data)
    idx = HNSWIndex(cfg, data.shape[1])
    lo = 0
    for sz in splits:
        idx.add_batch(data[lo: lo + sz])
        lo += sz
    _assert_frozen_identical(idx.freeze(), ref)


def test_incremental_adds_amortized(reference):
    """Re-ingest is amortized doubling: O(log n) buffer reallocations, not
    one per add_batch call (the seed reconcatenated everything each call)."""
    data, cfg, _ = reference
    idx = HNSWIndex(cfg, data.shape[1])
    reallocs = 0
    prev = id(idx._vstack)
    for lo in range(0, len(data), 50):
        idx.add_batch(data[lo: lo + 50])
        if id(idx._vstack) != prev:
            reallocs += 1
            prev = id(idx._vstack)
    assert idx._cap >= len(data)
    # 16 adds of 50 points: growth from the initial capacity to >=800 takes
    # at most a handful of doublings, never one realloc per call
    assert reallocs <= int(np.log2(len(data))) + 1


def test_worker_count_invariance(monkeypatch):
    """workers=0 (in-process) and workers=2 (real ProcessPoolExecutor)
    produce bit-identical per-partition graphs: partitions are isolated,
    each seeded from the same config.  The workers are spawned, never
    forked from a parent that may hold an accelerator."""
    data = _corpus(n=1200, d=12, seed=5)
    cfg = LannsConfig(num_shards=1, num_segments=4, segmenter="rh",
                      engine="hnsw", hnsw_m=8, ef_construction=40,
                      ef_search=40)
    methods = []
    get_context = multiprocessing.get_context

    def spy(method=None):
        methods.append(method)
        return get_context(method)

    monkeypatch.setattr(multiprocessing, "get_context", spy)
    a = LannsIndex(cfg).build(data, workers=0)
    b = LannsIndex(cfg).build(data, workers=2)
    assert methods == ["spawn"]
    assert a.build_stats["build_workers"] == 0
    assert b.build_stats["build_workers"] == 2
    assert set(a.partitions) == set(b.partitions)
    for sg in a.partitions:
        _assert_frozen_identical(a.partitions[sg].frozen,
                                 b.partitions[sg].frozen)


@pytest.mark.parametrize("chunk", [32, 512])
def test_lanns_chunk_invariance(chunk):
    """The chunk knob threads through LannsIndex.build to every partition
    without changing the built graphs."""
    data = _corpus(n=1000, d=12, seed=9)
    cfg = LannsConfig(num_shards=1, num_segments=2, segmenter="rh",
                      engine="hnsw", hnsw_m=8, ef_construction=40,
                      ef_search=40)
    a = LannsIndex(cfg).build(data)
    b = LannsIndex(cfg).build(data, chunk=chunk)
    assert b.build_stats["build_chunk"] == chunk
    for sg in a.partitions:
        _assert_frozen_identical(a.partitions[sg].frozen,
                                 b.partitions[sg].frozen)


def test_resume_round_trip(tmp_path):
    """A build killed midway and resumed yields the same frozen graphs as
    an uninterrupted build, and keeps merged build-cost provenance."""
    data = _corpus(n=1200, d=12, seed=5)
    keys = np.arange(len(data), dtype=np.int64)
    cfg = LannsConfig(num_shards=1, num_segments=4, segmenter="rh",
                      engine="hnsw", hnsw_m=8, ef_construction=40,
                      ef_search=40)
    full = LannsIndex(cfg).build(data, keys)

    rdir = str(tmp_path / "resume")
    idx = LannsIndex(cfg)
    idx.fit(data)
    assignment = idx.partitioner.assign(data, keys)
    for g in (0, 1):
        rows = assignment.rows[0][g]
        s, gg, payload, _ = _build_one_partition(
            (0, g, data[rows], keys[rows], "hnsw", cfg.hnsw_config(), 256)
        )
        idx._save_partition(rdir, s, gg, payload)

    resumed = LannsIndex(cfg)
    resumed.fit(data)
    resumed.build(data, keys, resume_dir=rdir)
    assert set(resumed.partitions) == set(full.partitions)
    for sg in full.partitions:
        _assert_frozen_identical(resumed.partitions[sg].frozen,
                                 full.partitions[sg].frozen)
    # the resumed run only rebuilt segments 2 and 3 but its summary merged
    # the manifest-persisted provenance of the earlier run (none here: the
    # partial build above wrote partitions without a manifest, so the
    # summary covers the two partitions this run actually built)
    summary = resumed.build_stats["per_partition_seconds_summary"]
    assert summary["count"] == 2


def test_recall_parity_bulk_vs_legacy():
    """The wavefront builder's graphs must search as well as the seed's
    sequential builder — same frozen-search path, so recall isolates the
    build: gap bounded at 0.03 on this corpus (acceptance at bench scale
    is 0.01, checked in bench_build_query_scaling)."""
    data = _corpus(n=1500, d=24, seed=1)
    rng = np.random.default_rng(2)
    queries = rng.standard_normal((64, 24)).astype(np.float32)
    cfg = HNSWConfig(seed=7)
    _, gt = brute_force_topk(queries, data, 10)
    recalls = {}
    for name, cls in (("bulk", HNSWIndex), ("legacy", HNSWIndexLegacy)):
        frozen = cls(cfg, 24).add_batch(data).freeze()
        _, ids = frozen.search(queries, 10, ef=120)
        recalls[name] = recall_at_k(np.asarray(ids), np.asarray(gt), 10)
    assert recalls["bulk"] >= 0.85
    assert abs(recalls["bulk"] - recalls["legacy"]) <= 0.03


def test_seconds_summary_helpers():
    assert _summarize_seconds([]) == {}
    s = _summarize_seconds([3.0, 1.0, 2.0])
    assert s == {"min": 1.0, "median": 2.0, "max": 3.0, "total": 6.0,
                 "count": 3}
    # identity on empty sides
    assert _merge_seconds_summary({}, s) == s
    assert _merge_seconds_summary(s, {}) == s
    m = _merge_seconds_summary(s, _summarize_seconds([5.0]))
    assert m["min"] == 1.0 and m["max"] == 5.0
    assert m["total"] == 11.0 and m["count"] == 4
    # merged median is count-weighted, bounded by the inputs
    assert 2.0 <= m["median"] <= 5.0


def test_manifest_persists_summary_not_raw_seconds(tmp_path):
    """save() drops the per-partition timing dict (it scales with partition
    count) but keeps the compact summary, so resumed builds retain
    build-cost provenance."""
    data = _corpus(n=600, d=12, seed=4)
    cfg = LannsConfig(num_shards=1, num_segments=4, segmenter="rh",
                      engine="hnsw", hnsw_m=8, ef_construction=40,
                      ef_search=40)
    idx = LannsIndex(cfg).build(data)
    root = str(tmp_path / "saved")
    idx.save(root)
    with open(os.path.join(root, "manifest.json")) as f:
        manifest = json.load(f)
    stats = manifest["build_stats"]
    assert "per_partition_seconds" not in stats
    summary = stats["per_partition_seconds_summary"]
    assert summary["count"] == 4
    assert summary["min"] <= summary["median"] <= summary["max"]
    assert summary["total"] >= summary["max"]
