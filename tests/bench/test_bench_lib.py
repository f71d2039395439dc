"""The benchmark's yardstick on its own: discovery by name, the trace
reduction, the roofline arithmetic, the comparison and its bfloat16
control."""

import json
import os
import shutil
import sys

import numpy as np
import pytest

from bench_tiny import REPO, tiny_root, workloads

sys.path.insert(0, REPO)

from bench.lib import check, readers, roofline, spec  # noqa: E402
from bench.lib import trace as tracing  # noqa: E402

# -- discovery by name ----------------------------------------------------


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    """A cell added as files plus a BENCHMARK.json entry, no edit."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(root, "bench"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    before = {
        p: open(os.path.join(root, "bench", p), "rb").read()
        for p in ("configs/people50d.json", "traffic/batch1024_top200.json")
    }
    cfg = dict(json.load(open(os.path.join(root, "bench/configs/people50d.json"))),
               name="people96d", dim=96)
    json.dump(cfg, open(os.path.join(root, "bench/configs/people96d.json"), "w"))
    json.dump({"entry": "index.query", "batch": 64, "topk": 100,
               "table_batches": 4},
              open(os.path.join(root, "bench/traffic/batch64_top100.json"), "w"))
    with open(os.path.join(root, "bench/metrics/batches_read.py"), "w") as f:
        f.write("def read(rec):\n    return len(rec['batches']) or None\n")
    bench["configs"].append({"name": "people96d", "source": "x",
                             "file": "bench/configs/people96d.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "people96d.small",
                               "config": "people96d",
                               "traffic": "batch64_top100", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "batches_read", "unit": "1",
                               "better": "higher", "source": "program_span",
                               "layer": "x", "moves": "qps",
                               "workloads": ["people96d.small"]})
    for m in bench["end_to_end"]:  # the new cell reports the offline rate
        if m["name"] == "qps":
            m["workloads"].append("people96d.small")
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))

    cell = spec.load_cell(root, "people96d.small")
    assert cell.config["dim"] == 96 and cell.traffic["batch"] == 64
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "qps",
                                                   "recall_at_k"}
    assert [m["name"] for m in cell.per_layer] == ["batches_read"]
    read = spec.metric_reader(root, "batches_read")
    assert read({"batches": [1, 2]}) == 2 and read({"batches": []}) is None
    for p, data in before.items():  # nothing that was there changed
        assert open(os.path.join(root, "bench", p), "rb").read() == data
    # the cells already there resolve as before
    assert spec.load_cell(root, "people50d.offline").config["dim"] == 50


def test_every_per_layer_metric_has_a_reader():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        read = spec.metric_reader(REPO, m["name"])
        empty = {"batches": [], "trace": None, "peaks": None}
        assert read(empty) is None, m["name"]  # nothing to read: no number


def test_peaks_refuse_an_unknown_device_kind():
    assert spec.device_peaks(REPO, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.device_peaks(REPO, "TPU v9 imaginary")


# -- the trace reduction ----------------------------------------------------


def _synthetic_trace():
    ms = 1_000_000
    ops = [  # (device, module, op, start_ns, dur_ns)
        ("/device:TPU:0", "jit_scan(1)", "fusion.1", 0 * ms, 10 * ms),
        ("/device:TPU:0", "jit_scan(1)", "fusion.2", 5 * ms, 10 * ms),
        ("/device:TPU:0", "jit__rerank_gather_dev(2)", "gather", 40 * ms,
         10 * ms),
        ("/device:TPU:0", "jit_scan(1)", "fusion.1", 90 * ms, 20 * ms),
    ]
    host = [
        ("bench.window", 0, 100 * ms),
        ("bench.index_query", 0, 60 * ms),
        ("bench.submit", 70 * ms, 2 * ms),
    ]
    return ops, host


def test_trace_reduction_gives_busy_idle_ops_and_gap_labels():
    ops, host = _synthetic_trace()
    r = tracing.reduce(ops, host)
    assert r["window_s"] == pytest.approx(0.1)
    # busy [0, 15] + [40, 50] + [90, 100] (clipped to the window) = 35 ms
    assert r["busy_s"] == pytest.approx(0.035)
    assert dict(r["device_ops"]) == pytest.approx(
        {"jit_scan(1)": 0.030, "jit__rerank_gather_dev(2)": 0.010})
    gaps = dict(r["idle_gaps"])
    # gaps [15, 40] and [50, 60]... : [15, 40] mid 27.5 in index_query;
    # [50, 90] mid 70 in submit
    assert gaps == pytest.approx({"bench.index_query": 0.025,
                                  "bench.submit": 0.040})
    rec = {"trace": {"ops": ops, "window": tracing.window_of(host), **r},
           "batches": [{"least_s": 0.005, "stages": None, "b": 1}]}
    assert readers.idle_pct(rec) == pytest.approx(65.0)
    # the scan's device time leaves the re-rank's programs out: 25 ms
    assert readers.scan_roofline_pct(rec, ("_rerank_gather_dev",)) == \
        pytest.approx(20.0)
    assert tracing.reduce(ops, host[1:]) is None  # no window: nothing


# -- roofline arithmetic --------------------------------------------------


def test_roofline_arithmetic_on_a_hand_sized_case():
    # 2 queries, 3 partitions of 10/20/30 rows, d = 4, k = 5, float32
    routed = np.array([[1, 1, 0], [0, 1, 0]], bool)
    ops, nbytes = roofline.scan_work(routed, [10, 20, 30], 4, 5, 4)
    assert ops == 2 * 4 * (10 + 20 + 20)  # (q0: p0, p1), (q1: p1)
    # p0 and p1 read once (30 rows x 16 B), queries 2 x 16 B, answers 2 x 5 x 8
    assert nbytes == 30 * 16 + 2 * 16 + 2 * 5 * 8
    assert roofline.scan_work(routed, [10, 20, 30], 4, [5, 10], 4)[1] == \
        30 * 16 + 2 * 16 + 15 * 8
    peak, bw = 400.0, 592.0
    least = roofline.least_seconds(ops, nbytes, peak, bw)
    assert least == pytest.approx(1.0)  # bytes-bound: 592 B at 592 B/s
    assert roofline.roofline_pct(least, 4.0) == pytest.approx(25.0)
    # a device that ran the work in its least time reads 100%, never more
    assert roofline.roofline_pct(least, least) == pytest.approx(100.0)
    assert roofline.roofline_pct(least, 0.0) is None


# -- traffic ---------------------------------------------------------------


def test_every_seed_serves_the_same_batches_in_another_order():
    from bench.lib.entries import OfflineBatches

    traffic = {"batch": 8, "topk": 3, "table_batches": 4}
    entry = OfflineBatches(traffic)

    def first_passes(seed, n=3):
        gen = entry.passes(seed)
        return [next(gen) for _ in range(n)]

    def listed(passes):
        return [[r.tolist() for r in p] for p in passes]

    a, b = first_passes(2**33 + 5), first_passes(7)
    assert listed(a) == listed(first_passes(2**33 + 5))  # same seed, same
    for pa, pb in zip(a, b):  # each pass: every batch once, rows intact
        sets_a = sorted(tuple(sorted(r)) for r in pa)
        sets_b = sorted(tuple(sorted(r)) for r in pb)
        assert sets_a == sets_b
        assert sorted(np.concatenate(pa)) == list(range(entry.pool))
    assert any(not np.array_equal(x, y) for pa, pb in zip(a, b)
               for x, y in zip(pa, pb))


# -- the comparison and its control -----------------------------------------


def test_checks_catch_missing_malformed_and_wrong_distances():
    rng = np.random.default_rng(0)
    corpus = rng.standard_normal((50, 4)).astype(np.float32)
    q = rng.standard_normal((3, 4)).astype(np.float32)
    d = ((corpus[None] - q[:, None]) ** 2).sum(-1)
    ids = np.argsort(d, axis=1)[:, :5]
    dist = np.take_along_axis(d, ids, 1).astype(np.float32)
    sample = [(q[r], 5, ids[r], dist[r]) for r in range(3)]
    limits = {"dist_gap": 1e-4, "recall": 0.9}
    ok, checks = check.compare(sample, corpus, "l2", ids, limits,
                               unanswered=0)
    assert ok and checks["recall"]["value"] == 1.0
    assert not check.compare(sample, corpus, "l2", ids, limits,
                             unanswered=1)[0]
    dup = [(q[0], 5, np.r_[ids[0][:4], ids[0][0]], dist[0])] + sample[1:]
    assert check.compare(dup, corpus, "l2", ids, limits,
                         unanswered=0)[1]["malformed"]["value"] == 1
    off = [(q[0], 5, ids[0], dist[0] * 1.01)] + sample[1:]
    assert not check.compare(off, corpus, "l2", ids, limits,
                             unanswered=0)[0]


def test_the_bf16_control_comes_out_not_correct(tmp_path):
    from bench.control import control_checks

    root = tiny_root(str(tmp_path))
    for workload in workloads(root):
        cell = spec.load_cell(root, workload)
        correct, checks, _ = control_checks(cell, seed=3)
        assert not correct, workload
        assert checks["dist_gap"]["value"] > 3 * checks["dist_gap"]["limit"]
        # the same search in float32 is correct: it fails on precision
        assert control_checks(cell, seed=3, precision="f32")[0], workload
