"""From a profiler trace to device busy time, idle gaps and top device ops.

The run wraps its measured window in a host ``TraceAnnotation`` named
``bench.window`` and its own calls into the program in annotations named
``bench.<call>``.  ``load`` reads the ``.xplane.pb`` the profiler wrote
into plain tuples; ``reduce`` works on those tuples alone, so it can be
checked on a small synthetic trace:

* busy time: the union of the intervals in which an op ran on a device,
  clipped to the window, averaged over the devices;
* device ops: the programs (XLA modules) that took most device time;
* idle gaps: the gaps between busy intervals, each labelled by the
  innermost ``bench.*`` annotation open on the host at its midpoint.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: one plane per chip, "/device:TPU:0" and so on
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")


def start(directory: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # Python function events would swamp it
    jax.profiler.start_trace(directory, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def load(directory: str):
    """(device_ops, host_spans, layout) from the newest trace file.

    device_ops: (device, module, op, start_ns, dur_ns) for every event on a
    device plane's ops line; host_spans: (name, start_ns, dur_ns) of every
    ``bench.*`` host event; layout: {plane: [line names]}, for the log."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return [], [], {}
    pd = ProfileData.from_file(paths[-1])
    ops, host, layout = [], [], {}
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        layout[plane.name] = sorted(lines)
        if DEVICE_PLANE.match(plane.name) and OPS_LINE in lines:
            mods = sorted(
                (e.start_ns, e.start_ns + e.duration_ns, e.name)
                for e in lines[MODULES_LINE].events
            ) if MODULES_LINE in lines else []
            starts = [m[0] for m in mods]
            for e in lines[OPS_LINE].events:
                j = bisect.bisect_right(starts, e.start_ns) - 1
                mod = mods[j][2] if j >= 0 and e.start_ns < mods[j][1] else ""
                ops.append((plane.name, mod, e.name, e.start_ns, e.duration_ns))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith("bench."):
                        host.append((e.name, e.start_ns, e.duration_ns))
    return ops, host, layout


def window_of(host_spans) -> tuple[float, float] | None:
    for name, s, d in host_spans:
        if name == WINDOW:
            return s, s + d
    return None


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def busy_ns(device_ops, window, exclude_modules=()) -> float:
    """Union of op intervals inside ``window``, averaged over devices;
    ops of a module whose name holds one of ``exclude_modules`` are left
    out."""
    w0, w1 = window
    per_dev: dict = {}
    for dev, mod, _, s, d in device_ops:
        if any(x in mod for x in exclude_modules):
            continue
        s, e = max(s, w0), min(s + d, w1)
        if e > s:
            per_dev.setdefault(dev, []).append((s, e))
    if not per_dev:
        return 0.0
    total = sum(sum(e - s for s, e in _union(iv)) for iv in per_dev.values())
    return total / len(per_dev)


class _Labeler:
    """Innermost (shortest) ``bench.*`` span open at a time, window aside."""

    def __init__(self, host_spans):
        self.by_name = {}
        for name, s, d in sorted(host_spans, key=lambda x: x[1]):
            if name != WINDOW:
                self.by_name.setdefault(name, []).append((s, s + d))
        self.index = {}
        for name, iv in self.by_name.items():
            reach, best = [], (float("-inf"), 0)
            for j, (s, e) in enumerate(iv):
                if e > best[0]:
                    best = (e, j)
                reach.append(best)  # the span reaching furthest so far
            self.index[name] = ([s for s, _ in iv], reach)

    def __call__(self, t) -> str:
        best = None
        for name, (starts, reach) in self.index.items():
            j = bisect.bisect_right(starts, t) - 1
            if j < 0 or reach[j][0] <= t:
                continue
            s, e = self.by_name[name][reach[j][1]]
            if best is None or e - s < best[1]:
                best = (name, e - s)
        return best[0] if best else "no benchmark call open"


def reduce(device_ops, host_spans, top: int = 10) -> dict | None:
    """Busy and window seconds, top device programs, idle gaps by label.

    None where the trace holds no window annotation."""
    window = window_of(host_spans)
    if window is None:
        return None
    w0, w1 = window
    by_mod: dict = {}
    per_dev: dict = {}
    for dev, mod, op, s, d in device_ops:
        s, e = max(s, w0), min(s + d, w1)
        if e <= s:
            continue
        key = mod or op
        by_mod[key] = by_mod.get(key, 0.0) + (e - s) / 1e9
        per_dev.setdefault(dev, []).append((s, e))
    gaps: dict = {}
    label = _Labeler(host_spans)
    for iv in per_dev.values():
        edges = [w0] + [x for se in _union(iv) for x in se] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                lab = label((a + b) / 2)
                gaps[lab] = gaps.get(lab, 0.0) + (b - a) / 1e9 / len(per_dev)
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns(device_ops, window) / 1e9,
        "device_ops": sorted(by_mod.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(gaps.items(), key=lambda kv: -kv[1])[:top],
    }
