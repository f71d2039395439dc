"""Scan kernel's share of its roofline, in percent: the least time of the
window's scans (``bench/lib/roofline.py``, from each batch's own routing)
over the device time of every op in the traced window but the exact
re-rank's, whose programs are named below."""

from bench.lib.readers import scan_roofline_pct

#: jitted programs of the exact re-rank (``quant/rerank.py``)
RERANK_JITS = ("_rerank_gather_dev",)


def read(rec):
    return scan_roofline_pct(rec, RERANK_JITS)
