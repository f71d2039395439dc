"""The comparison that decides ``correct``.

Each number is held to a limit of its own:

* ``unanswered`` — requests due in the window that got no answer, not even
  a minute past its close, or were cancelled.  Exact: limit 0.
* ``malformed`` — answers with fewer than k corpus ids, a repeated id, or
  distances that are not finite and ascending.  Exact: limit 0.
* ``dist_gap`` — over the sampled answers, the widest gap between a
  returned distance and the reference's float64 distance of the same id,
  as a share of the larger of that distance and the sample's median
  distance.  It holds every layer that produces a distance (the scan
  kernel, the q8 re-rank, the merge that carries it) to float32; its limit
  is set from the program's readings and the bfloat16 control's.
* ``recall`` — mean recall@k of the sampled answers against the exact
  top-k for each request's own k.  It guards routing and candidate
  generation, which can return wrong rows with their true distances; its
  limit is set from the program's readings and those of the ``misroute``
  fault (``bench/faults.py``).
"""

from __future__ import annotations

import numpy as np

from bench.lib.reference import exact_distances


def malformed_rows(ids: list, dists: list, n: int) -> int:
    """Answers (one (k,) ids and dists array each) that are not k distinct
    corpus ids with finite ascending distances."""
    bad = 0
    for i, d in zip(ids, dists):
        i = np.asarray(i)
        d = np.asarray(d)
        if (
            i.size == 0
            or (i < 0).any()
            or (i >= n).any()
            or len(np.unique(i)) != i.size
            or not np.isfinite(d).all()
            or (np.diff(d) < 0).any()
        ):
            bad += 1
    return bad


def dist_gap(prog_d: list, ref_d: list) -> float:
    """Widest relative gap between returned and reference distances, over
    the entries whose id is a corpus row (the others are malformed)."""
    ref_all = np.concatenate([np.asarray(r, np.float64) for r in ref_d])
    ref_all = ref_all[np.isfinite(ref_all)]
    if ref_all.size == 0:
        return 0.0
    scale = float(np.median(np.abs(ref_all)))
    worst = 0.0
    for p, r in zip(prog_d, ref_d):
        p = np.asarray(p, np.float64)
        r = np.asarray(r, np.float64)
        ok = np.isfinite(r) & np.isfinite(p)
        if ok.any():
            den = np.maximum(np.abs(r[ok]), scale)
            worst = max(worst, float(np.max(np.abs(p[ok] - r[ok]) / den)))
    return worst


def recall(prog_ids: list, true_ids: list) -> float:
    """Mean share of each request's exact top-k found in its answer."""
    vals = [
        len(set(np.asarray(p).tolist()) & set(np.asarray(t).tolist()))
        / len(t)
        for p, t in zip(prog_ids, true_ids)
    ]
    return float(np.mean(vals))


def compare(sample, corpus, metric, ref_ids, limits, *, unanswered: int):
    """The checks of one run.

    ``sample``: list of (query (d,), k, ids (k,), dists (k,)) answers drawn
    from the window; ``ref_ids``: (len(sample), >= max k) exact ids.
    ``limits``: {"dist_gap": max, "recall": min}.  Returns (correct,
    checks), checks being {name: {"value", "limit"}} in a fixed order.
    """
    if not sample:  # nothing answered: nothing is right
        checks = {"unanswered": {"value": int(unanswered), "limit": 0},
                  "malformed": {"value": 0, "limit": 0},
                  "dist_gap": {"value": 0.0, "limit": limits["dist_gap"]},
                  "recall": {"value": 0.0, "limit": limits["recall"]}}
        return False, checks
    q = np.stack([s[0] for s in sample])
    ids = [np.asarray(s[2]) for s in sample]
    dists = [np.asarray(s[3]) for s in sample]
    kmax = max(s[1] for s in sample)
    pad = np.full((len(sample), kmax), -1, np.int64)
    for r, i in enumerate(ids):
        pad[r, : len(i)] = i
    ref = exact_distances(corpus, q, pad, metric)
    ref_d = [ref[r, : len(i)] for r, i in enumerate(ids)]
    true = [ref_ids[r, : s[1]] for r, s in enumerate(sample)]
    checks = {
        "unanswered": {"value": int(unanswered), "limit": 0},
        "malformed": {
            "value": malformed_rows(ids, dists, len(corpus)), "limit": 0
        },
        "dist_gap": {
            "value": dist_gap(dists, ref_d), "limit": limits["dist_gap"]
        },
        "recall": {"value": recall(ids, true), "limit": limits["recall"]},
    }
    correct = (
        checks["unanswered"]["value"] <= 0
        and checks["malformed"]["value"] <= 0
        and checks["dist_gap"]["value"] <= checks["dist_gap"]["limit"]
        and checks["recall"]["value"] >= checks["recall"]["limit"]
    )
    return bool(correct), checks


def format_checks(checks: dict) -> list:
    """One plain line per number: name, value, the limit it is held to."""
    lines = []
    for name, c in checks.items():
        op = ">=" if name == "recall" else "<="
        lines.append(f"check {name} {c['value']!r} {op} {c['limit']!r}")
    return lines
