"""The program's spans on the profiler clock, read by ``bench/spans.py``:
the reductions on a trace sized by hand, and one traced window of each
cell at a tiny size on the CPU."""

import sys

import numpy as np
import pytest

from bench_tiny import REPO, tiny_root, workloads

sys.path.insert(0, REPO)

from bench import spans  # noqa: E402
from bench.lib import trace as tracing  # noqa: E402

SEED = 2**31 + 2**30 + 4321
MS = 1_000_000


def _synthetic_trace():
    ops = [  # TPU:0 idle [10, 30], [40, 70], [80, 100]; TPU:1 never idle
        ("/device:TPU:0", "jit_scan(1)", "fusion.1", 0, 10 * MS),
        ("/device:TPU:0", "jit_scatter(2)", "scatter", 30 * MS, 10 * MS),
        ("/device:TPU:0", "jit_scan(1)", "fusion.1", 70 * MS, 10 * MS),
        ("/device:TPU:1", "jit_scan(1)", "fusion.1", 0, 100 * MS),
    ]
    host = [
        ("bench.window", 0, 100 * MS),
        ("bench.index_query", 0, 90 * MS),
        ("lanns.route", 0, 5 * MS),
        ("lanns.candidates", 5 * MS, 80 * MS),
        ("lanns.scan.upload", 12 * MS, 13 * MS),
        ("lanns.scan.wait", 25 * MS, 20 * MS),
        ("lanns.scan.upload", 50 * MS, 10 * MS),
        ("lanns.scan.wait", 60 * MS, 15 * MS),
        ("lanns.merge", 85 * MS, 3 * MS),
    ]
    return ops, host


def test_idle_is_cut_at_span_edges_and_given_to_the_innermost_span():
    ops, host = _synthetic_trace()
    window = tracing.window_of(host)
    got = spans.idle_by_span(ops, host, window)
    # TPU:0's 70 ms of idle, cut at span edges ([10, 30] goes 2 / 13 / 5 to
    # candidates / upload / wait, not wholly to the upload at its
    # midpoint), averaged with TPU:1's none
    want_ms = {"lanns.scan.upload": 23, "lanns.scan.wait": 20,
               "lanns.candidates": 12, spans.NO_SPAN: 12,
               "lanns.merge": 3}
    assert got == pytest.approx({k: v / 2 / 1e3 for k, v in want_ms.items()})
    assert list(got)[0] == "lanns.scan.upload"  # largest first
    # every idle nanosecond is given to exactly one label
    r = tracing.reduce(ops, [h for h in host if h[0].startswith("bench.")])
    assert sum(got.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    # the upload's share: exact overlap of idle and upload spans
    assert spans.idle_overlap_pct(ops, host, window, "lanns.scan.upload") \
        == pytest.approx(11.5)
    assert spans.idle_overlap_pct(ops, host, window, "lanns.rerank") == 0.0
    assert spans.idle_overlap_pct([], host, window, "lanns.scan.upload") \
        is None


def test_plan_readings_are_per_batch_means():
    evs = [
        {"kind": "plan", "b": 4, "scan_s": {"upload": 0.5, "wait": 2.0},
         "h2d_bytes": 4 * 1024 * 10},
        {"kind": "plan", "b": 4, "scan_s": {"upload": 0.25, "wait": 1.0},
         "h2d_bytes": 4 * 1024 * 30},
    ]
    got = spans.plan_readings(evs, 2)
    assert got == pytest.approx({"upload_ms": 375.0, "scan_wait_ms": 1500.0,
                                 "h2d_kib_per_query": 20.0})
    # a program whose plan events carry no scan spans: nothing to read
    old = [{"kind": "plan", "b": 4, "stage_s": {"route": 0.1}}]
    assert set(spans.plan_readings(old, 1).values()) == {None}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny copy; JAX's compile-cache settings are put back after."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    yield tiny_root(str(tmp_path_factory.mktemp("bench")))
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def test_a_traced_window_holds_the_program_spans_inside_its_calls(root):
    for name in workloads(root):
        out = spans.run(root, name, SEED, 0.5, require_chip=False)
        host = out["spans"]
        calls = [(s, s + d) for n, s, d in host if n == "bench.index_query"]
        assert calls
        for want in ("lanns.route", "lanns.candidates", "lanns.scan.upload",
                     "lanns.scan.wait", "lanns.merge"):
            found = [(s, s + d) for n, s, d in host if n == want]
            assert found, want
            for s, e in found:  # nested inside one of the benchmark's calls
                assert any(a <= s and e <= b for a, b in calls), want
        for key in ("upload_ms", "scan_wait_ms", "h2d_kib_per_query"):
            assert np.isfinite(out[key]) and out[key] > 0, key
        # the counted bytes are what the partitions and routing reckon
        assert out["h2d_kib_per_query"] == pytest.approx(
            out["h2d_kib_per_query_reckoned"], rel=1e-12)
        assert out["window"]["batches"] >= 1
