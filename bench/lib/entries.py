"""How a traffic mix drives the program: one class per entry point.

A traffic file names its ``entry``; the class here reads the file's
parameters and nothing else, so a new mix for an existing entry is a new
data file.  Each entry warms the shapes its own traffic uses, then runs
the measured window and hands back plain numbers and answers:

* ``index.query`` — offline batches: ``LannsIndex.query`` called back to
  back on the batches of a fixed query table.  The window runs whole
  passes over the table, each in an order drawn from the run's seed, and
  closes at the end of the first pass that ends ``seconds`` or more after
  it opened; the rate is the queries of all its passes over its length.
  Every seed so serves the same work, in another order.
"""

from __future__ import annotations

import dataclasses
import time
from contextlib import nullcontext

import numpy as np


def _annotate(on: bool):
    if not on:
        return lambda name: nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


@dataclasses.dataclass
class Window:
    """What a measured window hands back."""

    metrics: dict  # end-to-end values by name
    attempted: int
    failed: int
    # one (query row, k, ids, dists) per query due in the window, in
    # order; None where no answer came
    answers: list
    # one (query rows, ks, executor stage seconds or None) per batch
    batches: list
    # answers of one whole pass over the table; ``answers`` holds whole
    # passes, one after another
    per_pass: int
    log: dict = dataclasses.field(default_factory=dict)


def _plan_stages(events):
    """Executor stage seconds of one batch: its ``plan`` spans (one per
    knob group) summed by stage; None where no span was read."""
    out = {}
    for ev in events:
        if ev["kind"] == "plan":
            for k, v in ev["stage_s"].items():
                out[k] = out.get(k, 0.0) + v
    return out or None


class OfflineBatches:
    """``LannsIndex.query`` on the batches of a fixed query table."""

    def __init__(self, traffic: dict):
        self.batch = int(traffic["batch"])
        self.topk = int(traffic["topk"])
        self.table_batches = int(traffic["table_batches"])
        self.pool = self.batch * self.table_batches

    def passes(self, seed):
        """Row arrays of the table's batches, pass after pass: the batches
        of each pass, and the rows of each batch, in an order drawn from
        ``seed``; a batch always holds the same rows."""
        rng = np.random.default_rng([seed, 1])
        while True:
            yield [b * self.batch + rng.permutation(self.batch)
                   for b in rng.permutation(self.table_batches)]

    def warm(self, index, queries, seed):
        """One pass over the table: the window serves the same batches, so
        this compiles every shape the window uses, and no other."""
        for rows in next(self.passes(seed)):
            index.query(queries[rows], self.topk)

    def window(self, index, queries, seconds, seed, *, tel=None,
               trace=False):
        note = _annotate(trace)
        out, stages, ends = [], [], []
        passes = self.passes(seed)
        t0 = time.perf_counter()
        with note("bench.window"):
            while True:
                for rows in next(passes):
                    mark = None if tel is None else tel.spans.next_seq
                    with note("bench.index_query"):
                        d, i = index.query(queries[rows], self.topk)
                    out.append((rows, np.asarray(d), np.asarray(i)))
                    t = time.perf_counter()
                    ends.append(t - t0)
                    if tel is not None:
                        stages.append(
                            _plan_stages(tel.spans.events(since=mark)))
                if t - t0 >= seconds:
                    break
        elapsed = t - t0
        n = len(out) * self.batch
        answers, batches = [], []
        for j, (rows, d, i) in enumerate(out):
            batches.append((rows, np.full(self.batch, self.topk),
                            stages[j] if stages else None))
            answers += [(r, self.topk, i[m], d[m]) for m, r in enumerate(rows)]
        return Window(
            metrics={"qps": n / elapsed}, attempted=n, failed=0,
            answers=answers, batches=batches, per_pass=self.pool,
            log={"window_s": elapsed, "batches": len(out),
                 "batch_s": [float(f(np.diff(ends, prepend=0.0)))
                             for f in (np.min, np.median, np.max)]},
        )


ENTRIES = {"index.query": OfflineBatches}
