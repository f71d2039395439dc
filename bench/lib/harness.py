"""One run of one cell: set-up, measured window, reference, result line.

``run`` is the whole benchmark behind ``bench/run.py``.  It prints its
progress as JSON objects on earlier lines of standard output, the numbers
compared for ``correct`` as the last lines of standard error, and returns
the result object that ``run.py`` prints as the last line of standard
output.
"""

from __future__ import annotations

import gc
import json
import os
import resource
import shutil
import sys
import tempfile
import time

import numpy as np

from bench.lib import check, spec
from bench.lib import trace as tracing
from bench.lib.entries import ENTRIES


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(**fields):
    print(json.dumps(fields, default=float), flush=True)


def _devices(cell, require_chip):
    import jax

    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX's default device is {devs[0].platform!r}"
                         f" ({devs[0].device_kind})")
        if len(devs) < cell.chips:
            raise NoChip(f"{cell.name} needs {cell.chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[: cell.chips]


def _compile_cache(root):
    """JAX's persistent cache at a fixed path inside the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names one (JAX reads that itself)."""
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        cache = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache


def _work(index, queries, batches, d, itemsize, peaks, peak_key):
    """Least seconds of each batch's scan, from its own routing."""
    from bench.lib import roofline

    if peaks is None:
        return [None] * len(batches)
    parts = sorted(index.partitions)
    rows = np.array([index.partitions[p].size for p in parts])
    seg = [g for _, g in parts]
    out = []
    for rws, ks, _ in batches:
        mask = index.partitioner.route_queries(queries[rws])[:, seg]
        ops, nbytes = roofline.scan_work(mask, rows, d, ks, itemsize)
        out.append(roofline.least_seconds(
            ops, nbytes, peaks[peak_key], peaks["hbm_bytes_per_s"]))
    return out


class Setup:
    """A cell made ready for its window: data, built index, warm shapes."""

    def __init__(self, root, workload, seed, *, require_chip=True):
        self.cell = cell = spec.load_cell(root, workload)
        self.devs = _devices(cell, require_chip)
        import jax

        self.cache = _compile_cache(root)
        from bench.lib.compile_meter import CompileMeter

        self.meter = CompileMeter()
        src = os.path.join(root, "src")
        if not os.path.isdir(os.path.join(src, "repro")):
            raise FileNotFoundError(f"the program under test is not at {src}")
        sys.path.insert(0, src)
        from repro.core.lanns import LannsConfig, LannsIndex

        from bench.lib.data import Mixture

        dev = self.devs[0]
        try:
            self.peaks = spec.device_peaks(root, dev.device_kind)
        except KeyError:
            if require_chip:
                raise
            self.peaks = None
        cfg = cell.config
        n, d = int(cfg["rows"]), int(cfg["dim"])
        self.entry = ENTRIES[cell.traffic["entry"]](cell.traffic)
        log(cell=workload, seed=seed, device=dev.device_kind,
            devices=len(jax.devices()), cache=self.cache, rows=n, dim=d,
            entry=cell.traffic["entry"])
        t = time.perf_counter()
        self.mix = Mixture(int(cfg["data_seed"]), n, d)
        self.corpus = self.mix.corpus()
        self.queries = self.mix.queries(self.entry.pool)
        self.parts = {"data_s": time.perf_counter() - t}
        t = time.perf_counter()
        self.index = LannsIndex(LannsConfig(**cfg["lanns"])).build(self.corpus)
        self.parts["build_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.entry.warm(self.index, self.queries, seed)
        self.parts["warm_s"] = time.perf_counter() - t


def run(root, workload, seed, seconds, trace, *, t_start,
        require_chip=True, fault=None):
    """Run ``workload`` once; returns the result object.

    ``fault`` (tests only) is called with the built index before the
    window and may break the timed path underneath."""
    from bench.lib.reference import exact_topk

    su = Setup(root, workload, seed, require_chip=require_chip)
    cell, cfg, dev, devs = su.cell, su.cell.config, su.devs[0], su.devs
    meter, mix, corpus, queries = su.meter, su.mix, su.corpus, su.queries
    index, entry, peaks, setup = su.index, su.entry, su.peaks, su.parts
    del su
    d = int(cfg["dim"])
    metric = cfg["lanns"]["metric"]
    if fault is not None:
        fault(index)
    tel = None
    if trace:
        from repro.obs import Telemetry
        from repro.obs.spans import SpanSink

        tel = Telemetry(spans=SpanSink(capacity=1 << 20))
        index.attach_telemetry(tel)
    before = meter.snapshot()
    setup.update(before)
    setup["setup_s"] = time.perf_counter() - t_start
    log(setup=setup, seconds=seconds, trace=trace)

    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        tracing.start(trace_dir)
    try:
        win = entry.window(index, queries, seconds, seed, tel=tel,
                           trace=bool(trace))
    finally:
        if trace:
            tracing.stop()
    after = meter.snapshot()
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    log(window=win.log, window_compiles=after["compiles"] - before["compiles"],
        window_cache_misses=after["cache_misses"] - before["cache_misses"],
        peak_bytes_in_use=peak, bytes_limit=stats.get("bytes_limit"))

    record = None
    if trace:
        itemsize = 1 if cfg["lanns"].get("quantized") == "q8" else 4
        peak_key = "int8_ops_per_s" if itemsize == 1 else "bf16_flops_per_s"
        least = _work(index, queries, win.batches, d, itemsize, peaks,
                      peak_key)
        ops, host, layout = tracing.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(trace_layout=layout, device_ops=len(ops), host_spans=len(host))
        summary = tracing.reduce(ops, host)
        record = {
            "batches": [{"b": len(r), "stages": st, "least_s": ls}
                        for (r, _, st), ls in zip(win.batches, least)],
            "trace": None if summary is None else {
                "ops": ops, "window": tracing.window_of(host), **summary},
            "peaks": peaks,
        }
        index.attach_telemetry(None)
    del index, entry, tel
    gc.collect()

    # the reference, once the window has closed and the program is freed
    t = time.perf_counter()
    # every answer of one whole pass over the table, the pass drawn from
    # the seed: each query of the table once
    per = win.per_pass
    p = int(np.random.default_rng(seed).integers(len(win.answers) // per))
    sample = [(queries[a[0]],) + tuple(a[1:])
              for a in win.answers[p * per: (p + 1) * per] if a is not None]
    ref_ids = None
    if sample:
        kmax = max(s[1] for s in sample)
        _, ref_ids = exact_topk(mix, np.stack([s[0] for s in sample]), kmax,
                                metric)
    correct, checks = check.compare(
        sample, corpus, metric, ref_ids, cfg["limits"],
        unanswered=win.failed,
    )
    log(reference_s=time.perf_counter() - t, checked=len(sample),
        host_peak_bytes=1024 * resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss)

    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(root, m["name"])(record)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = dict(win.metrics, setup_s=setup["setup_s"],
                      recall_at_k=checks["recall"]["value"])
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": int(win.attempted),
              "failed": int(win.failed), "metrics": metrics, "device": device}
    if trace and record["trace"] is not None:
        tr = record["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in tr["device_ops"]],
            "idle_gaps": [[k, v] for k, v in tr["idle_gaps"]],
        }
    result["checks"] = checks
    for line in check.format_checks(checks):
        print(line, file=sys.stderr, flush=True)
    return result
