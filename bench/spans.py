"""The program's own spans on the device trace's clock: where the idle goes.

    python bench/spans.py --workload people50d.offline --seed 7 --seconds 51

With a ``repro.obs.Telemetry`` attached, the executor marks its stages on
the profiler's host plane, which shares the device trace's clock:
``lanns.route``, ``lanns.candidates``, ``lanns.rerank`` and
``lanns.merge`` once per knob group, and inside the fp32 scan, once per
routed partition, ``lanns.scan.upload`` (the host->device copy of corpus
and queries) and ``lanns.scan.wait`` (the host waiting for the kernel's
answers).  Each ``plan`` span event carries the group's ``scan_s``
(upload and wait seconds) and ``h2d_bytes``.

This runs one traced window of a cell as ``bench/run.py --trace 1`` does
(the same set-up, warm-up, ``Telemetry`` and profiler), reads the trace
file once, and prints one JSON line: the window's batch times, the
per-batch readings of the plan events, the hand reckoning of the bytes
uploaded, the device's idle share, the idle share that overlaps an upload,
and the window's device idle seconds by the innermost program span open
through them.  The benchmark's own runs never run it, and it checks
nothing for ``correct``.
"""

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PREFIXES = ("bench.", "lanns.")
#: the label of idle time that no program span covers
NO_SPAN = "no program span"


def load(directory):
    """(device_ops, host_spans) of the newest trace file, read once.

    device_ops as ``bench.lib.trace.load`` gives them; host_spans: (name,
    start_ns, dur_ns) of every ``bench.*`` and ``lanns.*`` host event."""
    import bisect

    from jax.profiler import ProfileData

    from bench.lib import trace as tracing

    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return [], []
    pd = ProfileData.from_file(paths[-1])
    ops, host = [], []
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if tracing.DEVICE_PLANE.match(plane.name) and tracing.OPS_LINE in lines:
            mods = sorted(
                (e.start_ns, e.start_ns + e.duration_ns, e.name)
                for e in lines[tracing.MODULES_LINE].events
            ) if tracing.MODULES_LINE in lines else []
            starts = [m[0] for m in mods]
            for e in lines[tracing.OPS_LINE].events:
                j = bisect.bisect_right(starts, e.start_ns) - 1
                mod = mods[j][2] if j >= 0 and e.start_ns < mods[j][1] else ""
                ops.append((plane.name, mod, e.name, e.start_ns, e.duration_ns))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(PREFIXES):
                        host.append((e.name, e.start_ns, e.duration_ns))
    return ops, host


def idle_intervals(device_ops, window):
    """{device: sorted, disjoint (start, end) idle intervals in window},
    for each device with an op in the window."""
    from bench.lib import trace as tracing

    w0, w1 = window
    per_dev: dict = {}
    for dev, _, _, s, d in device_ops:
        s, e = max(s, w0), min(s + d, w1)
        if e > s:
            per_dev.setdefault(dev, []).append((s, e))
    out = {}
    for dev, iv in per_dev.items():
        edges = [w0] + [x for se in tracing._union(iv) for x in se] + [w1]
        out[dev] = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    return out


def _overlaps(a, b):
    """(index into b, overlap ns) of each overlapping pair of two sorted
    lists of disjoint intervals."""
    i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            yield j, hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1


def _pieces(spans, window):
    """The window cut at every program span's edges: (start, end, label),
    labelled by the innermost (shortest) ``lanns.*`` span open through the
    whole piece, or ``NO_SPAN``."""
    w0, w1 = window
    prog = [(s, s + d, name) for name, s, d in spans
            if name.startswith("lanns.")]
    cuts = sorted({w0, w1} | {t for s, e, _ in prog for t in (s, e)
                              if w0 < t < w1})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        open_ = [(e - s, name) for s, e, name in prog if s <= a and e >= b]
        out.append((a, b, min(open_)[1] if open_ else NO_SPAN))
    return out


def idle_by_span(device_ops, host_spans, window):
    """Device idle seconds in the window by the innermost program span
    open through them, averaged over devices; idle intervals are cut at
    span edges, so each idle nanosecond goes to the span it lay in."""
    idle = idle_intervals(device_ops, window)
    pieces = _pieces(host_spans, window)
    out: dict = {}
    for iv in idle.values():
        for j, ns in _overlaps(iv, pieces):
            label = pieces[j][2]
            out[label] = out.get(label, 0.0) + ns / 1e9 / len(idle)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def idle_overlap_pct(device_ops, host_spans, window, name):
    """Device idle time in the window that overlaps a ``name`` host span,
    over the window's length, in percent, averaged over devices; None
    where no device ran an op in the window."""
    from bench.lib import trace as tracing

    idle = idle_intervals(device_ops, window)
    if not idle:
        return None
    spans = tracing._union((s, s + d) for n, s, d in host_spans if n == name)
    ns = sum(ns for iv in idle.values() for _, ns in _overlaps(iv, spans))
    return 100.0 * ns / len(idle) / (window[1] - window[0])


def plan_readings(events, n_batches):
    """Per-batch means of the plan events' scan upload and wait times (ms)
    and uploaded KiB per query; None where no event carries them."""
    evs = [e for e in events if "scan_s" in e]
    if not evs or n_batches == 0:
        return {"upload_ms": None, "scan_wait_ms": None,
                "h2d_kib_per_query": None}
    queries = sum(e["b"] for e in evs)
    return {
        "upload_ms": 1e3 * sum(e["scan_s"]["upload"] for e in evs) / n_batches,
        "scan_wait_ms": 1e3 * sum(e["scan_s"]["wait"] for e in evs)
        / n_batches,
        "h2d_kib_per_query": sum(e["h2d_bytes"] for e in evs) / queries
        / 1024,
    }


def reckon_h2d_kib_per_query(index, queries, batches):
    """The bytes the fp32 scan must upload, from the partitions' padded
    scan corpora and each batch's routing: every routed partition's corpus
    and its routed queries, padded to a power of two, in float32."""
    total = n = 0
    parts = sorted(index.partitions.items())
    for rows, _, _ in batches:
        mask = index.partitioner.route_queries(queries[rows])
        for (_, g), part in parts:
            routed = int(mask[:, g].sum())
            if routed and part.size:
                pow2 = 1 << (routed - 1).bit_length()
                total += part.scan_corpus().nbytes
                total += pow2 * queries.shape[1] * 4
        n += len(rows)
    return total / n / 1024 if n else None


def run(root, workload, seed, seconds, *, require_chip=True):
    """One traced window of ``workload``; returns the readings."""
    from bench.lib import harness
    from bench.lib import trace as tracing

    su = harness.Setup(root, workload, seed, require_chip=require_chip)
    from repro.obs import Telemetry
    from repro.obs.spans import SpanSink

    tel = Telemetry(spans=SpanSink(capacity=1 << 20))
    su.index.attach_telemetry(tel)
    trace_dir = tempfile.mkdtemp(prefix="bench_spans_")
    tracing.start(trace_dir)
    try:
        win = su.entry.window(su.index, su.queries, seconds, seed, tel=tel,
                              trace=True)
    finally:
        tracing.stop()
    t = time.perf_counter()
    ops, host = load(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    load_s = time.perf_counter() - t
    window = tracing.window_of(host)
    out = {"cell": workload, "seed": seed,
           "device": su.devs[0].device_kind, "window": win.log,
           "trace_load_s": load_s, "device_ops": len(ops),
           "host_spans": len(host)}
    out.update(plan_readings(tel.spans.events(kind="plan"),
                             len(win.batches)))
    out["h2d_kib_per_query_reckoned"] = reckon_h2d_kib_per_query(
        su.index, su.queries, win.batches)
    if window is not None:
        summary = tracing.reduce(
            ops, [h for h in host if h[0].startswith("bench.")])
        busy = summary["busy_s"]
        out.update(
            window_s=summary["window_s"], busy_s=busy,
            device_idle=(100.0 * (1.0 - busy / summary["window_s"])
                         if busy > 0 else None),
            device_idle_upload=idle_overlap_pct(ops, host, window,
                                                "lanns.scan.upload"),
            idle_by_span=idle_by_span(ops, host, window),
            top_device_ops=summary["device_ops"],
            idle_gaps=summary["idle_gaps"],
        )
    out["spans"] = host
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from bench.lib import harness

    try:
        out = run(ROOT, args.workload, args.seed, args.seconds)
    except harness.NoChip as e:
        print(str(e), file=sys.stderr)
        return 2
    out.pop("spans")
    print(json.dumps(out, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
