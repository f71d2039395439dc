"""Query-plan executor: knob grouping, mixed-batch parity, merge decision.

The tentpole contract: a heterogeneous per-request (topk, ef) batch must be
BIT-IDENTICAL to issuing each knob group as its own homogeneous query —
grouping and reassembly may not perturb a single value.  Plus the merge
deprecation-window endpoint: ``choose_merge_path`` is the ONE place the
disjoint/two-level decision lives.
"""

import numpy as np
import pytest

from repro.core import LannsConfig, LannsIndex
from repro.core.lanns import _Partition
from repro.core.merge import per_shard_topk
from repro.core.plan import QueryPlanExecutor, choose_merge_path, knob_groups
from repro.data.synthetic import clustered_vectors


@pytest.fixture(scope="module")
def world():
    data = clustered_vectors(3000, 16, n_clusters=24, seed=0)
    queries = clustered_vectors(48, 16, n_clusters=24, seed=1)
    return data, queries


def _index(data, engine, **kw):
    cfg = LannsConfig(
        num_shards=1, num_segments=4, segmenter="apd", engine=engine,
        hnsw_m=8, ef_construction=40, ef_search=40, **kw,
    )
    return LannsIndex(cfg).build(data)


# ---------------------------------------------------------------------------
# knob_groups normalization
# ---------------------------------------------------------------------------


def test_knob_groups_scalar_and_collapse():
    scalar, groups = knob_groups(10, None, 4)
    assert scalar and groups == [(10, None, None)]
    scalar, groups = knob_groups(10, 64, 4)
    assert scalar and groups == [(10, 64, None)]
    # a homogeneous ARRAY collapses to the scalar fast path
    scalar, groups = knob_groups(np.full(4, 10), np.zeros(4, int), 4)
    assert scalar and groups == [(10, None, None)]
    scalar, groups = knob_groups(np.full(4, 10), np.full(4, 32), 4)
    assert scalar and groups == [(10, 32, None)]


def test_knob_groups_mixed_deterministic():
    tk = np.array([5, 10, 5, 10, 20])
    ef = np.array([0, 0, 64, 0, 0])
    scalar, groups = knob_groups(tk, ef, 5)
    assert not scalar
    # sorted by (topk, ef); rows ascending; every row exactly once
    assert [(t, e) for t, e, _ in groups] == [
        (5, None), (5, 64), (10, None), (20, None)
    ]
    rows = np.concatenate([r for _, _, r in groups])
    assert sorted(rows.tolist()) == list(range(5))
    np.testing.assert_array_equal(groups[0][2], [0])
    np.testing.assert_array_equal(groups[1][2], [2])
    np.testing.assert_array_equal(groups[2][2], [1, 3])


def test_knob_groups_validation():
    with pytest.raises(ValueError, match="topk"):
        knob_groups(0, None, 2)
    with pytest.raises(ValueError, match="topk"):
        knob_groups(np.array([5, 0]), None, 2)
    with pytest.raises(ValueError, match="shape"):
        knob_groups(np.array([5, 5, 5]), None, 2)
    with pytest.raises(ValueError, match="ef"):
        knob_groups(5, np.array([1, 2, 3]), 2)
    # empty batch with array knobs: no groups
    scalar, groups = knob_groups(np.zeros(0, int), None, 0)
    assert not scalar and groups == []


# ---------------------------------------------------------------------------
# mixed-batch bit-identity (the tentpole acceptance test)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ["scan", "hnsw"])
def test_mixed_knobs_bit_identical_to_homogeneous(world, engine):
    data, queries = world
    idx = _index(data, engine)
    B = len(queries)
    rng = np.random.default_rng(3)
    tk = rng.choice([5, 10, 20], B)
    ef = rng.choice([0, 48, 64], B)
    d, i, stats = idx.query(queries, tk, ef=ef, return_stats=True)
    assert d.shape == (B, tk.max()) and i.shape == (B, tk.max())
    # ef is an hnsw-only knob: the scan engine must NOT fragment its
    # batches on it (groups = distinct topk values only)
    want_groups = (
        len({(a, b) for a, b in zip(tk, ef)}) if engine == "hnsw"
        else len(set(tk))
    )
    assert stats["knob_groups"] == want_groups
    for tkv, efv in sorted({(a, b) for a, b in zip(tk, ef)}):
        rows = np.nonzero((tk == tkv) & (ef == efv))[0]
        dd, ii = idx.query(
            queries[rows], int(tkv), ef=(int(efv) if efv > 0 else None)
        )
        assert np.array_equal(i[rows, :tkv], ii), (engine, tkv, efv)
        assert np.array_equal(d[rows, :tkv], dd), (engine, tkv, efv)
        # rows narrower than the widest topk carry (+inf, -1) padding
        assert (i[rows, tkv:] == -1).all()
        assert np.isinf(d[rows, tkv:]).all()


def test_mixed_knobs_single_request_groups(world):
    """Every request its own knob group — the B=1-per-group worst case."""
    data, queries = world
    idx = _index(data, "scan")
    tk = np.array([3, 7, 11, 15])
    d, i = idx.query(queries[:4], tk)
    for j, tkv in enumerate(tk):
        dd, ii = idx.query(queries[j: j + 1], int(tkv))
        assert np.array_equal(i[j, :tkv], ii[0])
        assert np.array_equal(d[j, :tkv], dd[0])


def test_mixed_knobs_empty_batch(world):
    data, _ = world
    idx = _index(data, "scan")
    empty = np.zeros((0, data.shape[1]), np.float32)
    d, i, stats = idx.query(
        empty, np.zeros(0, np.int64), ef=np.zeros(0, np.int64),
        return_stats=True,
    )
    assert d.shape == (0, 0) and i.shape == (0, 0)
    assert stats["knob_groups"] == 0
    # merge_path report is configuration state — same as the scalar B==0
    # path (scan + virtual spill here)
    assert stats["merge_path"] == "disjoint"
    # same schema as scalar-knob stats (dashboards index unconditionally)
    _, _, full = idx.query(data[:2], 5, return_stats=True)
    assert set(stats) == set(full)
    assert full["knob_groups"] == 1


def test_homogeneous_array_matches_scalar(world):
    data, queries = world
    idx = _index(data, "scan")
    d1, i1 = idx.query(queries, np.full(len(queries), 10), ef=None)
    d2, i2 = idx.query(queries, 10)
    assert np.array_equal(i1, i2) and np.array_equal(d1, d2)


def test_scalar_ef_nonpositive_means_default(world):
    """Scalar ef <= 0 must follow the same 'index default' contract as
    array entries — a scalar 0 and a homogeneous array of 0 agree with
    ef=None bit-for-bit."""
    data, queries = world
    idx = _index(data, "hnsw")
    d_none, i_none = idx.query(queries[:8], 10, ef=None)
    d_zero, i_zero = idx.query(queries[:8], 10, ef=0)
    d_arr, i_arr = idx.query(queries[:8], 10, ef=np.zeros(8, np.int64))
    assert np.array_equal(i_none, i_zero) and np.array_equal(d_none, d_zero)
    assert np.array_equal(i_none, i_arr) and np.array_equal(d_none, d_arr)
    scalar, groups = knob_groups(10, -1, 4)
    assert scalar and groups == [(10, None, None)]


def test_warm_traces_covers_knob_mix(world):
    """warm_traces(knobs=...) pre-compiles every (topk, ef) pair's trace
    grid, so a mixed-knob workload adds NO scan traces at serve time (topk
    is a static jit arg — each distinct value is its own trace set)."""
    data, queries = world
    idx = _index(data, "scan")
    idx.warm_traces(8, 10, knobs=[(5, None), (20, 64)])
    _, _, s0 = idx.query(queries[:1], 10, return_stats=True)
    tk = np.array([5, 10, 20, 5, 10, 20, 5, 10])
    for b in (1, 3, 8):
        idx.query(queries[:b], tk[:b])
    _, _, s1 = idx.query(queries[:1], 10, return_stats=True)
    assert s1["scan_traces"] == s0["scan_traces"]


def test_mixed_knob_serving_zero_retrace(world, retrace_sentinel):
    """The sentinel twin of the stats-counter test above, over EVERY watched
    serving jit (scan, merge, rerank, ...) instead of just the scan kernel:
    a warmed mixed-knob workload recompiles nothing."""
    data, queries = world
    idx = _index(data, "scan")
    idx.warm_traces(8, 10, knobs=[(5, None), (20, 64)])
    tk = np.array([5, 10, 20, 5, 10, 20, 5, 10])
    for b in (1, 3, 8):  # warm pass fills any best-effort residual traces
        idx.query(queries[:b], tk[:b])
    with retrace_sentinel.expect_no_retrace("mixed-knob serving"):
        for b in (1, 3, 8):
            idx.query(queries[:b], tk[:b])


def test_mixed_knobs_quantized_scan(world):
    data, queries = world
    idx = _index(data, "scan", quantized="q8")
    tk = np.array([5, 15] * (len(queries) // 2))
    d, i = idx.query(queries, tk)
    for tkv in (5, 15):
        rows = np.nonzero(tk == tkv)[0]
        dd, ii = idx.query(queries[rows], tkv)
        assert np.array_equal(i[rows, :tkv], ii)
        assert np.array_equal(d[rows, :tkv], dd)


# ---------------------------------------------------------------------------
# the ONE merge-path decision point
# ---------------------------------------------------------------------------


def test_choose_merge_path_decision_table():
    mk = lambda **kw: LannsConfig(
        num_shards=1, num_segments=4, segmenter="apd", **kw
    )
    assert choose_merge_path(mk(engine="scan", spill="virtual")) == "disjoint"
    assert choose_merge_path(mk(engine="scan", spill="physical")) == "two_level"
    assert choose_merge_path(mk(engine="hnsw", spill="virtual")) == "two_level"
    assert choose_merge_path(mk(engine="hnsw", spill="physical")) == "two_level"
    assert (
        choose_merge_path(mk(engine="hnsw", quantized="q8")) == "two_level"
    )
    # q8 scan: disjoint only when the two-stage executor served EVERY
    # non-empty partition
    cfg = mk(engine="scan", quantized="q8")

    class _P:
        size = 1

    parts = {(0, 0): _P(), (0, 1): _P()}
    assert choose_merge_path(cfg, {(0, 0), (0, 1)}, parts) == "disjoint"
    assert choose_merge_path(cfg, {(0, 0)}, parts) == "two_level"


def test_merge_path_reported_consistently(world):
    """The stats field and the decision function must agree per mode."""
    data, queries = world
    for engine, spill, want in (
        ("scan", "virtual", "disjoint"),
        ("scan", "physical", "two_level"),
        ("hnsw", "virtual", "two_level"),
    ):
        idx = _index(data[:1200], engine, spill=spill)
        _, _, stats = idx.query(queries[:4], 5, return_stats=True)
        assert stats["merge_path"] == want == choose_merge_path(idx.config)


# ---------------------------------------------------------------------------
# the fp32 scan pipeline: partition i+1 dispatched before i is fetched
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def scan_indexes(world):
    """Scan indexes by (metric, shards, small): ``small`` splits 240 rows
    over 8 segments, so every partition holds fewer rows than k."""
    data, _ = world
    cache = {}

    def get(metric, shards, small=False):
        key = (metric, shards, small)
        if key not in cache:
            cfg = LannsConfig(
                num_shards=shards, num_segments=8 if small else 4,
                segmenter="apd", engine="scan", metric=metric,
            )
            cache[key] = LannsIndex(cfg).build(data[:240] if small else data)
        return cache[key]

    return get


def _sequential_candidates(self, plan):
    """The executor's candidate stage as one ``search()`` a partition:
    each scan fetched before the next is uploaded."""
    index = self.index
    for g in range(index.config.num_segments):
        sel = plan.sels[g]
        if sel.size == 0:
            continue
        for s in range(index.config.num_shards):
            part = index.partitions.get((s, g))
            if part is None or part.size == 0:
                continue
            d, i = part.search(plan.queries[sel], plan.pstk)
            plan.cand_d[sel, s, plan.slot[sel, g], : plan.pstk] = d
            plan.cand_i[sel, s, plan.slot[sel, g], : plan.pstk] = i
    return plan


def _query_with_slots(idx, queries, k, monkeypatch, candidates=None):
    """``idx.query`` with the candidate slots it merged; ``candidates`` in
    place of the executor's stage when given."""
    stage = QueryPlanExecutor.candidates if candidates is None else candidates
    slots = []

    def spy(self, plan):
        stage(self, plan)
        slots.append((plan.cand_d.copy(), plan.cand_i.copy()))
        return plan

    with monkeypatch.context() as m:
        m.setattr(QueryPlanExecutor, "candidates", spy)
        d, i = idx.query(queries, k)
    return d, i, slots


def _route(idx, monkeypatch, routing):
    """Route every query to no segment, to segment 0 alone, or to all."""
    m = idx.config.num_segments
    cols = {"none": [], "one": [0], "every": list(range(m))}[routing]

    def route(q):
        mask = np.zeros((len(q), m), bool)
        mask[:, cols] = True
        return mask

    monkeypatch.setattr(idx.partitioner, "route_queries", route)


def _assert_pipelined_matches_search(idx, queries, k, monkeypatch):
    d, i, slots = _query_with_slots(idx, queries, k, monkeypatch)
    d0, i0, slots0 = _query_with_slots(
        idx, queries, k, monkeypatch, _sequential_candidates)
    assert len(slots) == len(slots0) == 1
    for got, want in zip(slots[0], slots0[0]):
        assert np.array_equal(got, want)
    assert np.array_equal(i, i0) and np.array_equal(d, d0)
    return d, i, slots[0]


@pytest.mark.parametrize("routing", ["none", "one", "every"])
@pytest.mark.parametrize("shards", [1, 2])
@pytest.mark.parametrize("metric", ["l2", "ip", "cos", "mips"])
def test_pipelined_scan_bit_identical_to_per_partition_search(
        world, scan_indexes, monkeypatch, metric, shards, routing):
    """Dispatching partition i+1 before fetching i changes only the order
    of host calls: the candidate slots and the merged answers are the
    bits of one ``search()`` a partition, ids mapped through ``keys``."""
    _, queries = world
    idx = scan_indexes(metric, shards)
    _route(idx, monkeypatch, routing)
    d, i, _ = _assert_pipelined_matches_search(
        idx, queries, 10, monkeypatch)
    if routing == "none":
        assert (i == -1).all() and np.isinf(d).all()
    else:
        assert (i >= 0).all()


@pytest.mark.parametrize("shards", [1, 2])
@pytest.mark.parametrize("metric", ["l2", "ip", "cos", "mips"])
def test_pipelined_scan_pads_partitions_smaller_than_k(
        world, scan_indexes, monkeypatch, metric, shards):
    _, queries = world
    idx = scan_indexes(metric, shards, small=True)
    k = 64
    pstk = per_shard_topk(k, shards, idx.config.topk_confidence)
    sizes = [p.size for p in idx.partitions.values()]
    assert 0 < min(sizes) and max(sizes) < pstk
    _, _, (cand_d, cand_i) = _assert_pipelined_matches_search(
        idx, queries, k, monkeypatch)
    # every lane's last slot is padding (inf, -1), routed or not
    assert cand_i.shape[-1] == pstk
    assert (cand_i[..., -1] == -1).all() and np.isinf(cand_d[..., -1]).all()
    assert (cand_i >= 0).any()


def test_scan_pipeline_order_and_depth(world, scan_indexes, monkeypatch):
    """Scan i+1 is dispatched before scan i is fetched, every scan is
    fetched once, and no more than two are ever un-fetched; ``search()``
    alone still fetches its scan before it returns."""
    _, queries = world
    idx = scan_indexes("l2", 2)
    _route(idx, monkeypatch, "every")
    events = []
    dispatch, collect = _Partition.dispatch, _Partition.collect

    def spy_dispatch(self, q, k, *, spans):
        pending = dispatch(self, q, k, spans=spans)
        events.append(("dispatch", id(pending)))
        return pending

    def spy_collect(self, pending, *, spans):
        events.append(("collect", id(pending)))
        return collect(self, pending, spans=spans)

    monkeypatch.setattr(_Partition, "dispatch", spy_dispatch)
    monkeypatch.setattr(_Partition, "collect", spy_collect)
    idx.query(queries, 10)
    order = [p for step, p in events if step == "dispatch"]
    assert len(order) == len(idx.partitions) == 8
    assert sorted(p for step, p in events if step == "collect") == sorted(
        order)
    at = {e: n for n, e in enumerate(events)}
    for a, b in zip(order, order[1:]):
        assert at[("dispatch", b)] < at[("collect", a)]
    unfetched = depth = 0
    for step, _ in events:
        unfetched += 1 if step == "dispatch" else -1
        depth = max(depth, unfetched)
    assert depth == 2
    events.clear()
    part = idx.partitions[(0, 0)]
    part.search(queries[:3], 10)
    assert [step for step, _ in events] == ["dispatch", "collect"]
