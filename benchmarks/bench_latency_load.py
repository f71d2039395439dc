"""Paper §7 / Table 8: latency percentiles vs offered load, async host loop.

Protocol: build a scan-engine index, anchor the load axis with a CLOSED-loop
saturation measurement (enough synchronous clients to keep full micro-batches
forming — the achieved QPS is node capacity), then sweep an OPEN-loop Poisson
arrival process at fractions of that capacity (one point past it, where
queueing delay dominates — the upturn of the paper's p99 curve).  Every point
runs through ``AsyncAnnFrontend`` + ``serve/loadgen.py``, so latencies are
end-to-end (submit -> results visible) and include batching delay; fixed-
rate and bursty (two-state on/off MMPP) points at the same half load
bracket the Poisson point from below and above — the burstiness ladder
isolates the arrival-process share of the tail.

Emits the usual CSV rows plus ``BENCH_latency_load.json`` (schema in
``benchmarks/common.py``): per-point QPS, p50/p95/p99, formed-batch
histogram, and the headline ``saturation_qps`` metric that CI's regression
gate watches.  ``--smoke`` shrinks corpus and windows for the CI wiring leg.

Every point runs with a shared ``repro.obs.Telemetry``, so the per-stage
(queue/route/candidates/rerank/merge) decomposition of the half-load point
is printed as a table and exported three ways: info-gated metrics in
``BENCH_stage_breakdown.json``, the full Prometheus text exposition in
``BENCH_stage_breakdown.prom``, and the bounded span log in
``BENCH_stage_breakdown.jsonl``.

``--controller-ab`` runs the closed-loop acceptance experiment instead: an
HNSW index (the engine where ``ef`` actually buys latency), an MMPP burst
at 0.9x the measured saturation, and a paired controller-off/controller-on
comparison under per-request ``deadline_ms = slo_ms`` — emitted as
``BENCH_controller.json`` with SLO attainment, the on/off p99 ratio, and
recall on both legs (all info-gated while the policy calibrates; see
``INFO_MARKERS`` in ``benchmarks/check_regression.py``).
"""

from __future__ import annotations

import argparse
import math

import numpy as np

from benchmarks.common import (
    bench_payload,
    emit,
    ground_truth,
    sift_like_corpus,
    write_bench_json,
)
from repro.core import LannsConfig, LannsIndex
from repro.obs import Telemetry, format_stage_table
from repro.serve.loadgen import (
    LoadResult,
    measure_saturation_qps,
    run_controller_ab,
    run_load_point,
    sweep_load,
)


def _emit_point(prefix: str, res: LoadResult):
    label = (
        f"{prefix}.closed_c{res.concurrency}" if res.process == "closed"
        else f"{prefix}.{res.process}_q{res.offered_qps:.0f}"
    )
    emit(
        label,
        1e3 * res.mean_ms,  # us/query end-to-end
        f"qps={res.achieved_qps:.0f};p50_ms={res.p50_ms:.2f};"
        f"p95_ms={res.p95_ms:.2f};p99_ms={res.p99_ms:.2f};"
        f"mean_batch={res.mean_batch:.1f}",
    )


def run(
    n: int = 16_000,
    d: int = 64,
    topk: int = 100,
    duration_s: float = 2.0,
    max_batch: int = 64,
    max_wait_ms: float = 2.0,
    load_fracs=(0.25, 0.5, 0.75, 0.9, 1.1),
    out: str = "BENCH_latency_load.json",
    stage_out: str = "BENCH_stage_breakdown.json",
    smoke: bool = False,
    seed: int = 0,
):
    corpus, queries = sift_like_corpus(n, d, 2048, seed=31)
    cfg = LannsConfig(
        num_shards=1, num_segments=8, segmenter="apd", engine="scan",
        alpha=0.15,
    )
    idx = LannsIndex(cfg).build(corpus)
    tel = Telemetry()
    kw = {
        "topk": topk, "max_batch": max_batch, "max_wait_ms": max_wait_ms,
        "telemetry": tel,
    }
    # pre-compile the full serving trace set (every pow2 batch bucket x
    # corpus bucket) so no timed window pays an XLA compile — first-traffic
    # compiles are a deployment concern warm_traces exists to solve, not
    # part of the steady-state latency the sweep measures.
    idx.warm_traces(max_batch, topk)

    sat = measure_saturation_qps(
        idx, queries, duration_s=duration_s, **kw
    )
    _emit_point("latency_load", sat)
    sat2, points = sweep_load(
        idx, queries, load_fracs=load_fracs, process="poisson",
        duration_s=duration_s, saturation=sat, seed=seed, **kw,
    )
    for res in points:
        _emit_point("latency_load", res)
    # fixed-rate comparison point at half load: same mean rate, zero arrival
    # burstiness — the p99 gap vs the matching Poisson point is pure
    # arrival-process effect.
    fixed = run_load_point(
        idx, queries, process="fixed",
        rate_qps=max(0.5 * sat.achieved_qps, 1.0),
        duration_s=duration_s, seed=seed, **kw,
    )
    _emit_point("latency_load", fixed)
    # bursty comparison point at the SAME half load: two-state on/off MMPP
    # arrivals — queues build inside bursts, so its p99 sits between the
    # fixed-rate floor and the past-saturation blow-up and brackets Poisson
    # from above (burstiness ladder: fixed < poisson < mmpp).
    mmpp = run_load_point(
        idx, queries, process="mmpp",
        rate_qps=max(0.5 * sat.achieved_qps, 1.0),
        duration_s=duration_s, seed=seed, **kw,
    )
    _emit_point("latency_load", mmpp)

    # the *_half_load metrics must come from an EXACT 0.5x point (the fixed-
    # rate comparison is pinned there, and baselines gate it): take it from
    # the sweep when present, else run one extra point.
    fracs = list(load_fracs)
    if 0.5 in fracs:
        half = points[fracs.index(0.5)]
    else:
        half = run_load_point(
            idx, queries, process="poisson",
            rate_qps=max(0.5 * sat.achieved_qps, 1.0),
            duration_s=duration_s, seed=seed + len(fracs), **kw,
        )
        points = points + [half]
        _emit_point("latency_load", half)
    metrics = {
        "saturation_qps": sat.achieved_qps,
        "qps_poisson_half_load": half.achieved_qps,
        "qps_mmpp_half_load": mmpp.achieved_qps,
        "p50_ms_half_load": half.p50_ms,
        "p99_ms_half_load": half.p99_ms,
        "p99_ms_fixed_half_load": fixed.p99_ms,
        "p99_ms_mmpp_half_load": mmpp.p99_ms,
        "mean_batch_saturation": sat.mean_batch,
    }
    payload = bench_payload(
        "latency_load",
        config=dict(  # noqa: C408 -- kwargs mirror the CLI flag names
            n=n, d=d, topk=topk, duration_s=duration_s,
            max_batch=max_batch, max_wait_ms=max_wait_ms,
            load_fracs=list(load_fracs), seed=seed,
            num_segments=cfg.num_segments, segmenter=cfg.segmenter,
            engine=cfg.engine,
        ),
        metrics=metrics,
        rows=[sat.row()] + [p.row() for p in points] + [fixed.row(),
                                                        mmpp.row()],
        smoke=smoke,
    )
    write_bench_json(out, payload)

    # --- telemetry exports: the half-load point's per-stage decomposition
    # as its own (info-gated) bench payload, plus the raw Prometheus text
    # exposition and the span JSONL for offline drill-down.
    print("stage breakdown @ half load "
          f"(poisson, {half.offered_qps:.0f} qps offered):")
    print(format_stage_table(half.stage_breakdown))
    stage_metrics = {
        f"stage_{st}_{k}": v
        for st, pct in half.stage_breakdown.items()
        for k, v in pct.items()
        if isinstance(v, (int, float)) and math.isfinite(v)
    }
    stage_payload = bench_payload(
        "stage_breakdown",
        config=dict(  # noqa: C408
            n=n, d=d, topk=topk, duration_s=duration_s,
            max_batch=max_batch, offered_qps=half.offered_qps,
            process=half.process,
        ),
        metrics=stage_metrics,
        rows=[half.row()],
        smoke=smoke,
    )
    write_bench_json(stage_out, stage_payload)
    base = stage_out[:-5] if stage_out.endswith(".json") else stage_out
    with open(base + ".prom", "w") as fh:
        fh.write(tel.registry.expose_text())
    n_spans = tel.spans.dump_jsonl(base + ".jsonl")
    print(f"telemetry: {base}.prom + {base}.jsonl ({n_spans} spans, "
          f"{tel.spans.dropped} dropped)")
    return payload


def run_smoke(out: str = "BENCH_latency_load.json"):
    """CI wiring check: tiny corpus, sub-second windows, all three arrival
    processes exercised."""
    return run(
        n=3000, d=32, topk=20, duration_s=0.4, max_batch=16,
        max_wait_ms=2.0, load_fracs=(0.5, 0.9), out=out, smoke=True,
    )


def run_controller_ab_bench(
    n: int = 12_000,
    d: int = 64,
    topk: int = 50,
    duration_s: float = 2.0,
    max_batch: int = 32,
    max_wait_ms: float = 2.0,
    ef_ladder=(96, 64),
    hnsw_m: int = 12,
    ef_search: int = 128,
    out: str = "BENCH_controller.json",
    smoke: bool = False,
    seed: int = 0,
):
    """Closed-loop acceptance leg: controller-off vs controller-on under an
    MMPP burst at 0.9x saturation, per-request ``deadline_ms = slo_ms``.

    HNSW engine on purpose — ``ef`` is the dial the degrade ladder turns,
    and the scan engine ignores it.  Every ladder rung stays >= topk so a
    degraded request still fills its result slots (the recall cost of a
    rung is graceful, not a cliff).  The SLO itself is derived from the
    measured closed-loop anchor (a multiple of its mean end-to-end latency,
    floored at two batching windows) so the experiment tracks whatever
    hardware CI lands on instead of hard-coding milliseconds.
    """
    if min(ef_ladder) < topk:
        raise ValueError(
            f"ef_ladder {tuple(ef_ladder)} has rungs below topk={topk}; "
            "degrade would truncate result lists, not trade accuracy"
        )
    corpus, queries = sift_like_corpus(n, d, 1024, seed=31)
    cfg = LannsConfig(
        num_shards=1, num_segments=4, segmenter="apd", engine="hnsw",
        hnsw_m=hnsw_m, ef_construction=2 * ef_search, ef_search=ef_search,
        alpha=0.15,
    )
    idx = LannsIndex(cfg).build(corpus)
    gt_ids = np.asarray(ground_truth(corpus, queries, topk)[1])
    tel = Telemetry()
    kw = {
        "topk": topk, "max_batch": max_batch, "max_wait_ms": max_wait_ms,
        "telemetry": tel,
    }
    # warm the default knobs AND every ladder rung: a controller decision
    # must never trigger a compile mid-window (the zero-retrace contract
    # tests/test_controller.py pins).
    idx.warm_traces(max_batch, topk,
                    knobs=[(topk, ef) for ef in ef_ladder])

    sat = measure_saturation_qps(idx, queries, duration_s=duration_s, **kw)
    _emit_point("controller_ab", sat)
    # SLO anchor: the full-batch SERVICE time at saturation (mean_batch
    # queries drain per 1/qps-per-batch seconds), not the closed-loop
    # end-to-end mean — that includes queueing behind every closed-loop
    # client and would hand the controller an SLO nothing ever misses.
    # 2x service time is met at moderate load and blown inside MMPP
    # bursts, which is exactly the regime degrade exists for.
    service_ms = 1e3 * sat.mean_batch / max(sat.achieved_qps, 1e-9)
    slo_ms = max(2.0 * service_ms, 2.0 * max_wait_ms)
    rate_qps = max(0.9 * sat.achieved_qps, 1.0)
    off, on, ctrl = run_controller_ab(
        idx, queries, rate_qps=rate_qps, slo_ms=slo_ms,
        ef_ladder=tuple(ef_ladder), process="mmpp",
        duration_s=duration_s, seed=seed, gt_ids=gt_ids, **kw,
    )
    for tag, res in (("off", off), ("on", on)):
        emit(
            f"controller_ab.mmpp_{tag}",
            1e3 * res.mean_ms,
            f"qps={res.achieved_qps:.0f};p99_ms={res.p99_ms:.2f};"
            f"slo_attainment={res.slo_attainment:.3f};"
            f"recall={res.mean_recall:.4f};degraded={res.degraded}",
        )
    snap = ctrl.snapshot()
    print(
        f"controller: ticks={snap['ticks']} tighten={snap['tighten']} "
        f"relax={snap['relax']} hold={snap['hold']} "
        f"degraded={snap['degraded']} "
        f"max_wait_ms={snap['max_wait_ms']:.3f} (slo {slo_ms:.2f} ms)"
    )
    metrics = {
        # every key is info-gated (INFO_MARKERS: mmpp / slo_attainment /
        # p99_ratio) while the policy calibrates across runners; promote
        # slo_attainment_on + p99_ratio_on_off to gates once nightly
        # history shows they are stable.
        "slo_attainment_on": on.slo_attainment,
        "slo_attainment_off": off.slo_attainment,
        "p99_ratio_on_off": on.p99_ms / off.p99_ms if off.p99_ms else None,
        "p99_ms_mmpp_on": on.p99_ms,
        "p99_ms_mmpp_off": off.p99_ms,
        "recall_mmpp_on": on.mean_recall,
        "recall_mmpp_off": off.mean_recall,
        "degraded_mmpp_on": on.degraded,
        "slo_ms_mmpp": slo_ms,
    }
    payload = bench_payload(
        "controller_ab",
        config=dict(  # noqa: C408 -- kwargs mirror the CLI flag names
            n=n, d=d, topk=topk, duration_s=duration_s,
            max_batch=max_batch, max_wait_ms=max_wait_ms,
            ef_ladder=list(ef_ladder), hnsw_m=hnsw_m, ef_search=ef_search,
            seed=seed, rate_qps=rate_qps, slo_ms=slo_ms,
            num_segments=cfg.num_segments, engine=cfg.engine,
        ),
        metrics=metrics,
        rows=[sat.row(), off.row(), on.row()],
        smoke=smoke,
    )
    write_bench_json(out, payload)
    return payload


def run_controller_ab_smoke(out: str = "BENCH_controller.json"):
    """CI wiring check for the A/B leg: tiny HNSW corpus, short windows."""
    return run_controller_ab_bench(
        n=3000, d=32, topk=20, duration_s=0.4, max_batch=16,
        max_wait_ms=2.0, ef_ladder=(48, 24), hnsw_m=8, ef_search=64,
        out=out, smoke=True,
    )


if __name__ == "__main__":
    from repro.common.utils import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny corpus / short windows (CI wiring check)")
    ap.add_argument("--controller-ab", action="store_true",
                    help="run the closed-loop controller A/B leg instead "
                         "(emits BENCH_controller.json)")
    ap.add_argument("--out", default=None,
                    help="output JSON path (default depends on the leg)")
    args = ap.parse_args()
    if args.controller_ab:
        out = args.out or "BENCH_controller.json"
        (run_controller_ab_smoke(out) if args.smoke
         else run_controller_ab_bench(out=out))
    else:
        out = args.out or "BENCH_latency_load.json"
        run_smoke(out) if args.smoke else run(out=out)
