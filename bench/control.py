"""The control of ``correct``: the reference in the program's place, one
precision lower, must come out as not correct.

    python bench/control.py --workload people50d.offline --seeds 1 2 3

For each seed it draws the cell's corpus and query table as a run does,
answers every request of one pass over the table, in the seed's order, by
the bfloat16 brute force of ``bench/lib/reference.py`` — the step below the
float32 the configurations state — and prints the numbers ``bench/lib/check.py``
compares, beside the cell's limits, one JSON line a seed.  The benchmark's
own runs never run it; it is how the limits' upper readings were taken.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_checks(cell, seed, precision="bf16"):
    """The checks of the ``precision`` brute force on one pass's answers."""
    from bench.lib import check
    from bench.lib.data import Mixture
    from bench.lib.entries import ENTRIES
    from bench.lib.reference import exact_topk

    cfg, traffic = cell.config, cell.traffic
    metric = cfg["lanns"]["metric"]
    entry = ENTRIES[traffic["entry"]](traffic)
    mix = Mixture(int(cfg["data_seed"]), int(cfg["rows"]), int(cfg["dim"]))
    queries = mix.queries(entry.pool)
    q = queries[np.concatenate(next(entry.passes(seed)))]
    k = entry.topk
    t = time.perf_counter()
    d_c, i_c = exact_topk(mix, q, k, metric, precision=precision)
    _, ref_ids = exact_topk(mix, q, k, metric)
    sample = [(q[j], k, i_c[j], d_c[j]) for j in range(len(q))]
    correct, checks = check.compare(sample, mix.corpus(), metric, ref_ids,
                                    cfg["limits"], unanswered=0)
    return correct, checks, time.perf_counter() - t


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--precision", default="bf16", choices=("bf16", "f32"))
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax

    from bench.lib import spec

    cell = spec.load_cell(ROOT, args.workload)
    dev = jax.devices()[0]
    for seed in args.seeds:
        correct, checks, secs = control_checks(cell, seed, args.precision)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "precision": args.precision, "device": dev.device_kind,
                          "correct": correct, "seconds": secs,
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
