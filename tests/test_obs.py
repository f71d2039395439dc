"""Telemetry subsystem: metrics registry, span sink, pipeline hooks.

The acceptance contract (mirrored from the serving stack's): telemetry
OBSERVES, never participates — attaching it must not change a single
result bit, and every aggregate it keeps is bounded (fixed-bucket
histograms, a capacity-capped span ring, a fixed-depth query-stats
ring) so a long-lived server cannot leak through its own instruments.
"""

import json
import threading

import numpy as np
import pytest

from repro.common.utils import next_pow2
from repro.core import LannsConfig, LannsIndex
from repro.core.merge import per_shard_topk
from repro.data.synthetic import clustered_vectors
from repro.kernels import ops
from repro.obs import (
    Histogram,
    MetricsRegistry,
    SpanSink,
    Telemetry,
    format_stage_table,
    percentiles_ms,
    stage_breakdown,
)
from repro.serve.engine import AnnFrontend


@pytest.fixture(scope="module")
def small_index():
    data = clustered_vectors(1200, 16, n_clusters=8, seed=0)
    cfg = LannsConfig(num_shards=1, num_segments=4, segmenter="apd",
                      engine="scan")
    return LannsIndex(cfg).build(data)


@pytest.fixture(scope="module")
def queries():
    return clustered_vectors(32, 16, n_clusters=8, seed=1)


# ---------------------------------------------------------------------------
# histograms: bucket-boundary edge cases (the satellite's explicit ask)
# ---------------------------------------------------------------------------


def test_histogram_exact_boundary_lands_in_bucket():
    """Prometheus `le` semantics: a value EXACTLY on a bound counts in
    that bound's bucket (upper-inclusive), not the next one."""
    h = Histogram(buckets=(1.0, 2.0, 5.0))
    h.observe(1.0)   # on the first bound
    h.observe(2.0)   # on the second
    h.observe(1.5)   # strictly inside the second
    counts, total, count = h.snapshot()
    assert counts.tolist() == [1, 2, 0, 0]
    assert count == 3 and total == pytest.approx(4.5)


def test_histogram_overflow_bucket():
    h = Histogram(buckets=(1.0, 2.0))
    h.observe(2.0000001)  # just past the last bound
    h.observe(1e9)
    counts, _, count = h.snapshot()
    assert counts.tolist() == [0, 0, 2]  # both in the +Inf overflow slot
    assert count == 2
    # quantiles from an all-overflow population clamp to the last bound
    assert h.quantile(0.5) == 2.0


def test_histogram_observe_many_matches_loop():
    vals = [0.0003, 0.0005, 0.001, 0.0011, 0.049, 0.05, 0.051, 7.0]
    h1, h2 = Histogram(), Histogram()
    h1.observe_many(vals)
    for v in vals:
        h2.observe(v)
    c1, s1, n1 = h1.snapshot()
    c2, s2, n2 = h2.snapshot()
    assert np.array_equal(c1, c2) and n1 == n2 == len(vals)
    assert s1 == pytest.approx(s2)  # summation order differs (pairwise sum)
    h1.observe_many([])  # empty batch is a no-op
    assert h1.snapshot()[2] == len(vals)


def test_histogram_quantile_interpolates():
    h = Histogram(buckets=(1.0, 2.0, 4.0))
    h.observe_many([0.5] * 50 + [3.0] * 50)
    assert h.quantile(0.25) == pytest.approx(0.5)
    assert 2.0 <= h.quantile(0.9) <= 4.0
    assert np.isnan(Histogram().quantile(0.5))
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_histogram_validates_bounds():
    for bad in ((), (1.0, 1.0), (2.0, 1.0), (1.0, float("inf"))):
        with pytest.raises(ValueError):
            Histogram(buckets=bad)


# ---------------------------------------------------------------------------
# registry: idempotent registration, counters, pull gauges, exposition
# ---------------------------------------------------------------------------


def test_registry_idempotent_and_mismatch_raises():
    reg = MetricsRegistry()
    c1 = reg.counter("x_total", "help", ("a",))
    c2 = reg.counter("x_total", "other help", ("a",))
    assert c1 is c2  # same (name, kind, labels) -> the existing family
    with pytest.raises(ValueError):  # kind mismatch
        reg.gauge("x_total")
    with pytest.raises(ValueError):  # label-schema mismatch
        reg.counter("x_total", labelnames=("a", "b"))
    with pytest.raises(ValueError):  # invalid name
        reg.counter("9bad-name")


def test_counter_monotonic():
    reg = MetricsRegistry()
    c = reg.counter("ops_total")
    c.inc()
    c.inc(2.5)
    assert c.value == pytest.approx(3.5)
    with pytest.raises(ValueError):
        c.inc(-1.0)


def test_gauge_set_function_pull_mode():
    reg = MetricsRegistry()
    g = reg.gauge("depth")
    g.set(3.0)
    assert g.value == 3.0
    state = {"v": 7}
    g.set_function(lambda: state["v"])
    assert g.value == 7.0
    state["v"] = 9
    assert g.value == 9.0  # read at collection time, not registration
    g.set(1.0)  # a set() drops back to push mode
    assert g.value == 1.0


def test_labels_validation():
    reg = MetricsRegistry()
    fam = reg.counter("req_total", labelnames=("kind", "engine"))
    fam.labels("full", "scan").inc()
    fam.labels(kind="full", engine="scan").inc(2)
    assert fam.labels("full", "scan").value == 3.0
    with pytest.raises(ValueError):
        fam.labels("full")  # arity mismatch
    with pytest.raises(ValueError):
        fam.labels(kind="full")  # missing keyword


def test_expose_text_format():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests", ("kind",)).labels("full").inc(4)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe_many([0.05, 0.5, 2.0])
    text = reg.expose_text()
    assert "# HELP req_total requests" in text
    assert "# TYPE req_total counter" in text
    assert 'req_total{kind="full"} 4' in text
    # cumulative buckets + the +Inf total
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert "lat_seconds_count 3" in text
    assert text.endswith("\n")


def test_registry_concurrent_updates_are_exact():
    reg = MetricsRegistry()
    c = reg.counter("n_total")
    h = reg.histogram("v_seconds", buckets=(0.5,))

    def work():
        for _ in range(500):
            c.inc()
            h.observe(0.1)

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 2000.0
    assert h._default().snapshot()[2] == 2000


# ---------------------------------------------------------------------------
# span sink: bounded ring, watermark filtering, JSONL round trip
# ---------------------------------------------------------------------------


def test_span_sink_bounded_and_dropped():
    sink = SpanSink(capacity=4, clock=lambda: 123.0)
    for i in range(7):
        sink.emit("plan", i=i)
    assert len(sink) == 4
    assert sink.dropped == 3
    evs = sink.events()
    assert [e["i"] for e in evs] == [3, 4, 5, 6]  # oldest fell off
    assert all(e["ts"] == 123.0 for e in evs)
    with pytest.raises(ValueError):
        SpanSink(capacity=0)


def test_span_sink_kind_and_since_filters():
    sink = SpanSink(capacity=16)
    sink.emit("plan", x=1)
    mark = sink.next_seq
    sink.emit("batch", x=2)
    sink.emit("plan", x=3)
    assert [e["x"] for e in sink.events(kind="plan")] == [1, 3]
    assert [e["x"] for e in sink.events(since=mark)] == [2, 3]
    assert [e["x"] for e in sink.events(kind="plan", since=mark)] == [3]
    sink.clear()
    assert len(sink) == 0
    assert sink.next_seq == 3  # seq survives a clear (still a watermark)


def test_span_sink_jsonl_round_trip(tmp_path):
    sink = SpanSink(capacity=8)
    sink.emit("retrace", fn="scan", count=2)
    sink.emit("plan", stage_s={"route": 0.001})
    path = tmp_path / "spans.jsonl"
    assert sink.dump_jsonl(str(path)) == 2
    lines = [json.loads(li) for li in path.read_text().splitlines()]
    assert lines[0]["kind"] == "retrace" and lines[0]["count"] == 2
    assert lines[1]["stage_s"]["route"] == 0.001


def test_stage_breakdown_and_table():
    events = [
        {"kind": "plan", "stage_s": {"route": 0.001, "merge": 0.002}},
        {"kind": "plan", "stage_s": {"route": 0.003, "merge": 0.004}},
        {"kind": "batch", "b": 4},  # ignored: not a plan event
    ]
    bd = stage_breakdown(events, extra={"queue": [0.01, 0.02]})
    assert list(bd) == ["queue", "route", "merge"]  # canonical order
    assert bd["route"]["n"] == 2
    assert bd["queue"]["mean_ms"] == pytest.approx(15.0)
    table = format_stage_table(bd)
    assert "queue" in table and "p99_ms" in table
    empty = percentiles_ms([])
    assert empty["n"] == 0 and np.isnan(empty["p50_ms"])


# ---------------------------------------------------------------------------
# Telemetry bundle: pipeline hooks, bit-identity, retrace plumbing
# ---------------------------------------------------------------------------


def test_attach_telemetry_bit_identical(small_index, queries):
    """The tentpole invariant: instrumentation-off and -on return the same
    bits (the hooks only observe)."""
    idx = small_index
    d0, i0 = idx.query(queries, 10)
    tel = Telemetry()
    idx.attach_telemetry(tel)
    try:
        d1, i1 = idx.query(queries, 10)
    finally:
        idx.attach_telemetry(None)
    assert np.array_equal(np.asarray(d0), np.asarray(d1))
    assert np.array_equal(np.asarray(i0), np.asarray(i1))
    # and the executor recorded a plan span with the full stage split
    plans = tel.spans.events(kind="plan")
    assert plans, "no plan span recorded"
    assert set(plans[0]["stage_s"]) == {"route", "candidates", "rerank",
                                        "merge"}
    assert plans[0]["engine"] == "scan"
    assert "lanns_stage_seconds" in tel.registry.expose_text()


def test_scan_spans_and_transfer_bytes_match_a_hand_reckoning(
        small_index, queries):
    """The fp32 scan's upload and wait spans ride on the plan event, and
    its byte counts equal the padded corpus and queries of every routed
    partition, and the padded answers fetched back."""
    idx = small_index
    tel = Telemetry()
    idx.attach_telemetry(tel)
    try:
        idx.query(queries, 10)
    finally:
        idx.attach_telemetry(None)
    (ev,) = tel.spans.events(kind="plan")
    assert set(ev["scan_s"]) == {"upload", "wait"}
    assert min(ev["scan_s"].values()) >= 0.0
    cfg = idx.config
    pstk = per_shard_topk(10, cfg.num_shards, cfg.topk_confidence)
    routed = idx.partitioner.route_queries(queries).sum(axis=0)
    h2d = d2h = 0
    for (_, g), part in idx.partitions.items():
        n = int(routed[g])
        if n == 0 or part.size == 0:
            continue
        rows = next_pow2(n)
        h2d += part.scan_corpus().nbytes + rows * queries.shape[1] * 4
        d2h += rows * min(pstk, part.size) * (4 + 4)  # f32 dists, i32 ids
    assert h2d > 0
    assert ev["h2d_bytes"] == h2d
    assert tel.transfer_bytes.labels("h2d").value == h2d
    assert tel.transfer_bytes.labels("d2h").value == d2h
    text = tel.registry.expose_text()
    assert f'lanns_transfer_bytes_total{{direction="h2d"}} {h2d}' in text


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "interpret"])
def test_scan_calls_counted_by_path(small_index, queries, monkeypatch,
                                    kernel):
    """One count a routed partition, under the path ``distance_topk``
    took: the blocked jnp scan on the CPU, the binned path of the TPU
    through the Pallas interpreter."""
    if kernel:
        resolve = ops._resolve_backend
        monkeypatch.setattr(
            ops, "_resolve_backend",
            lambda b: "pallas_interpret" if b == "auto" else resolve(b))
    idx = small_index
    tel = Telemetry()
    idx.attach_telemetry(tel)
    try:
        idx.query(queries, 10)
    finally:
        idx.attach_telemetry(None)
    routed = idx.partitioner.route_queries(queries).sum(axis=0)
    want = {}
    for (_, g), part in idx.partitions.items():
        if routed[g] and part.size:
            path = ops.scan_path(part.scan_corpus().shape[0],
                                 min(10, part.size))
            want[path] = want.get(path, 0) + 1
    assert set(want) == ({"binned"} if kernel else {"blocked"})
    for path in ("binned", "direct", "blocked"):
        assert tel.scan_calls.labels(path).value == want.get(path, 0)
    text = tel.registry.expose_text()
    for path, n in want.items():
        assert f'lanns_scan_calls_total{{path="{path}"}} {n}' in text


def test_detached_telemetry_reads_no_clock(small_index, queries):
    def clock():
        raise AssertionError("clock read")

    idx = small_index
    tel = Telemetry(clock=clock)
    idx.attach_telemetry(tel)
    try:  # attached, the executor reads it: the probe is live
        with pytest.raises(AssertionError, match="clock read"):
            idx.query(queries, 10)
    finally:
        idx.attach_telemetry(None)
    d, i = idx.query(queries, 10)  # detached: never called
    assert d.shape == (len(queries), 10)
    assert tel.spans.events(kind="plan") == []


@pytest.mark.parametrize("engine", ["scan", "hnsw"])
def test_q8_rerank_span_is_split_out_of_candidates(engine):
    data = clustered_vectors(1200, 16, n_clusters=8, seed=0)
    q = clustered_vectors(16, 16, n_clusters=8, seed=1)
    cfg = LannsConfig(num_shards=1, num_segments=4, segmenter="apd",
                      engine=engine, quantized="q8", hnsw_m=8,
                      ef_construction=40, ef_search=40)
    idx = LannsIndex(cfg).build(data)
    d0, i0 = idx.query(q, 10)
    tel = Telemetry()
    idx.attach_telemetry(tel)
    try:
        d1, i1 = idx.query(q, 10)
    finally:
        idx.attach_telemetry(None)
    assert np.array_equal(d0, d1) and np.array_equal(i0, i1)
    (ev,) = tel.spans.events(kind="plan")
    st = ev["stage_s"]
    assert set(st) == {"route", "candidates", "rerank", "merge"}
    assert st["rerank"] > 0.0 and st["candidates"] >= 0.0
    assert ev["h2d_bytes"] == 0  # the fp32 scan did not run


def test_frontend_on_batch_counters(small_index, queries):
    idx = small_index
    tel = Telemetry()
    fe = AnnFrontend(idx, topk=5, max_batch=8, max_wait_ms=1e9,
                     telemetry=tel)
    idx.attach_telemetry(tel)
    try:
        for q in queries[:16]:
            fe.submit(q)
        fe.step()  # two full batches
    finally:
        idx.attach_telemetry(None)
    assert tel.requests_total.labels("full_batches").value == 16.0
    assert tel.batches_total.labels("full_batches").value == 2.0
    batch_evs = tel.spans.events(kind="batch")
    assert [e["b"] for e in batch_evs] == [8, 8]
    for e in batch_evs:
        assert e["queue_max_s"] >= e["queue_mean_s"] >= 0.0
    # the batched histograms saw every request exactly once
    assert tel.queue_seconds._default().snapshot()[2] == 16
    assert tel.latency_seconds._default().snapshot()[2] == 16


class _FakeSentinel:
    """retraced()/reset() stub: one pending retrace, then quiet."""

    def __init__(self):
        self.hot = {"beam_search": 2}
        self.resets = 0

    def retraced(self):
        return dict(self.hot)

    def reset(self):
        self.hot = {}
        self.resets += 1


def test_retrace_poll_plumbing():
    sent = _FakeSentinel()
    tel = Telemetry(sentinel=sent)
    hot = tel.poll_retraces()
    assert hot == {"beam_search": 2}
    assert sent.resets == 1
    assert tel.poll_retraces() == {}  # drained: counts fresh compiles only
    assert sent.resets == 1  # no reset when nothing retraced
    assert tel.retraces_total.labels("beam_search").value == 2.0
    evs = tel.spans.events(kind="retrace")
    assert len(evs) == 1 and evs[0]["fn"] == "beam_search"


def test_register_serve_engine_pull_gauges():
    class Stub:
        def __init__(self):
            self.stats = {"served": 5, "rejected": 0}

    eng = Stub()
    tel = Telemetry()
    tel.register_serve_engine(eng, prefix="stub")
    text = tel.registry.expose_text()
    assert "stub_served 5" in text
    eng.stats["served"] = 11  # pull mode: next collection sees the update
    assert "stub_served 11" in tel.registry.expose_text()


def test_serve_engine_registers_on_shared_registry():
    """One exposition covers both engines: the LM ServeEngine's stats dict
    registers as serve_engine_* pull gauges on the shared registry."""
    import jax

    from repro.models import transformer as tf
    from repro.serve.engine import Request, ServeEngine

    cfg = tf.TransformerConfig(n_layers=1, d_model=32, n_heads=2,
                               n_kv_heads=2, head_dim=16, d_ff=64, vocab=128)
    params = tf.init(jax.random.PRNGKey(0), cfg)
    tel = Telemetry()
    eng = ServeEngine(cfg, params, slots=2, max_seq=32, telemetry=tel)
    text = tel.registry.expose_text()
    for key in eng.stats:
        assert f"serve_engine_{key} " in text
    eng.submit(Request(0, np.arange(4, dtype=np.int32), max_new_tokens=2))
    eng.run()
    # pull mode: the next collection reads the live dict, no push needed
    assert "serve_engine_completed 1" in tel.registry.expose_text()


def test_recent_query_stats_ring(small_index, queries):
    idx = small_index
    fe = AnnFrontend(idx, topk=5, max_batch=4, max_wait_ms=1e9,
                     collect_stats=True, recent_stats_depth=3)
    for q in queries[:20]:
        fe.submit(q)
    fe.step()  # five batches of 4 -> ring keeps the newest 3
    recent = fe.recent_query_stats()
    assert len(recent) == 3
    assert fe.last_query_stats is recent[-1]
    assert fe.recent_query_stats(2) == recent[-2:]
    assert fe.recent_query_stats(99) == recent  # over-ask clamps
    assert fe.recent_query_stats(0) == []
    with pytest.raises(ValueError):
        AnnFrontend(idx, recent_stats_depth=0)
    # without collect_stats the ring stays empty and last is None
    fe2 = AnnFrontend(idx, topk=5, max_batch=4)
    fe2.submit(queries[0])
    fe2.flush()
    assert fe2.last_query_stats is None
    assert fe2.recent_query_stats() == []
