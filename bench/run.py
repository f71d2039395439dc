"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload people50d.offline --seed 7 \\
        --seconds 30 --trace 0

The cell, its configuration, traffic mix and per-layer metrics are found
by name from ``BENCHMARK.json`` (see ``bench/lib/spec.py``).  The run
refuses (exit 2, no result line) where JAX finds no TPU or fewer chips
than the cell asks for, and fails (exit 1) where the program cannot be
imported.  With ``--trace 0`` the result carries the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics from a traced window.
"""

import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from bench.lib import harness

    try:
        result = harness.run(ROOT, args.workload, args.seed, args.seconds,
                             args.trace, t_start=T_START)
    except harness.NoChip as e:
        print(str(e), file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
