"""Faults planted under the timed path, to show that ``correct`` catches them.

    python bench/faults.py --workload people50d.offline --fault misroute \\
        --seeds 1 2 3 --seconds 5

Each fault is called with the built index before the window and breaks the
path the window drives, where the answer is produced:

* ``alter_one_answer`` — the nearest id of every answer replaced by
  another corpus row, its distance left as it was;
* ``drop_half_the_batch`` — only the first half of each batch answered;
* ``misroute`` — the router's segment mask shifted by one segment, so
  each query scans segments that do not hold its neighbours and returns
  their rows with true distances.

For each seed the command runs the cell once with the fault (a whole run,
with its own set-up) and prints the numbers ``bench/lib/check.py``
compares beside their limits, one JSON line a seed.  The benchmark's own
runs never plant a fault; this is how the limits' upper readings of the
numbers that the bfloat16 control does not fail were taken.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def alter_one_answer(index):
    query = index.query

    def wrong(*a, **kw):
        d, i = query(*a, **kw)
        i = np.array(i)
        i[:, 0] = np.where(i[:, 0] > 0, i[:, 0] - 1, 1)
        return d, i

    index.query = wrong


def drop_half_the_batch(index):
    query = index.query

    def half(q, topk, **kw):
        h = max(len(q) // 2, 1)
        d, i = query(q[:h], topk if np.ndim(topk) == 0 else topk[:h], **kw)
        fill_d = np.full((len(q) - h, d.shape[1]), np.inf, np.float32)
        fill_i = np.full((len(q) - h, i.shape[1]), -1, np.int64)
        return np.concatenate([d, fill_d]), np.concatenate([i, fill_i])

    index.query = half


def misroute(index):
    route = index.partitioner.route_queries

    def shifted(q):
        return np.roll(route(q), 1, axis=1)

    index.partitioner.route_queries = shifted


FAULTS = {f.__name__: f for f in (alter_one_answer, drop_half_the_batch,
                                  misroute)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True, choices=sorted(FAULTS))
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from bench.lib import harness

    for seed in args.seeds:
        res = harness.run(ROOT, args.workload, seed, args.seconds, 0,
                          t_start=time.perf_counter(),
                          fault=FAULTS[args.fault])
        print(json.dumps({"workload": args.workload, "fault": args.fault,
                          "seed": seed, "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
