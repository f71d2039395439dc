"""Arithmetic shared by the per-layer metric readers in ``bench/metrics``.

A reader gets the traced run's record:

* ``batches``: one dict per batch served in the window — ``b`` queries,
  ``stages`` (the executor's ``Telemetry`` plan spans summed over the
  batch's knob groups: seconds of route, candidates, rerank, merge; None
  where no span was read) and ``least_s`` (the least time of its scan on
  this chip, from ``roofline``; None without a peaks entry);
* ``trace``: the reduced device trace (``ops``, ``window``, ``busy_s``,
  ``window_s``), or None where the trace held no window.

Every function returns None where it finds nothing to read.
"""

from __future__ import annotations

import numpy as np

from bench.lib import roofline
from bench.lib import trace as tracing


def stage_ms(rec, stages) -> float | None:
    """Mean over the window's batches of the named stages' summed time."""
    vals = [sum(b["stages"].get(s, 0.0) for s in stages)
            for b in rec["batches"] if b["stages"]]
    return 1e3 * float(np.mean(vals)) if vals else None


def scan_roofline_pct(rec, exclude_modules) -> float | None:
    """Least time of the window's scans over the device time of every op
    in the window but those of ``exclude_modules``."""
    tr = rec["trace"]
    least = [b["least_s"] for b in rec["batches"]]
    if tr is None or not least or any(v is None for v in least):
        return None
    dev_s = tracing.busy_ns(tr["ops"], tr["window"], exclude_modules) / 1e9
    return roofline.roofline_pct(float(sum(least)), dev_s)


def idle_pct(rec) -> float | None:
    tr = rec["trace"]
    if tr is None or tr["window_s"] <= 0 or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
