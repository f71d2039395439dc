"""Operations and bytes of a routed scan, from the algorithm.

The work is what the search needs, not what a kernel happens to do: each
routed (query, partition) pair scores every unpadded row of the partition
once (``2 * d`` operations a row), and each partition that any query of
the batch is routed to is read once per batch, plus the queries and the
(B, k) answers (a float32 distance and an int32 id each).  The least time
is the larger of operations over the peak rate and bytes over the memory
bandwidth; the roofline share is that least time over the device time the
work took.
"""

from __future__ import annotations

import numpy as np


def scan_work(routed: np.ndarray, rows: np.ndarray, d: int, k,
              corpus_itemsize: int) -> tuple[float, float]:
    """(operations, bytes) of one batch.

    ``routed``: (B, P) bool, query b is routed to partition p;
    ``rows``: (P,) unpadded rows of each partition; ``k``: the answers'
    length, one for the batch or one per query."""
    routed = np.asarray(routed, bool)
    rows = np.asarray(rows, np.float64)
    B = routed.shape[0]
    pairs_rows = float(routed.sum(axis=0) @ rows)
    ops = 2.0 * d * pairs_rows
    read = float(rows[routed.any(axis=0)].sum()) * d * corpus_itemsize
    answers = float(np.sum(np.broadcast_to(np.asarray(k, np.float64), (B,))))
    nbytes = read + B * d * 4.0 + answers * 8.0
    return ops, nbytes


def least_seconds(ops: float, nbytes: float, peak_ops: float,
                  bytes_per_s: float) -> float:
    """The least time the chip could take for this work."""
    return max(ops / peak_ops, nbytes / bytes_per_s)


def roofline_pct(least_s: float, device_s: float):
    """Least time as a share of the device time, in percent; None where no
    device time was read."""
    if device_s <= 0:
        return None
    return 100.0 * least_s / device_s
