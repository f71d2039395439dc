"""A tiny copy of the benchmark for the CPU tests of ``bench/``.

``tiny_root`` copies ``BENCHMARK.json`` and ``bench/`` into a directory,
shrinks every configuration and traffic mix to a size the CPU serves in a
second, and links the program's ``src``.  Nothing else changes: the same
harness, entries, reference, checks and readers run on it.
"""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

TINY_CONFIG = {"rows": 3000, "dim": 16}
TINY_LANNS = {"num_segments": 4, "segmenter_sample": 3000}
TINY_TRAFFIC = {
    "index.query": {"batch": 32, "topk": 10, "table_batches": 4},
}


def _load(path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f)


def tiny_root(dest: str) -> str:
    """A shrunken copy of the benchmark at ``dest``; returns ``dest``."""
    os.makedirs(dest, exist_ok=True)
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(dest, "src"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dest)
    spec = _load(os.path.join(dest, "BENCHMARK.json"))
    for c in spec["configs"]:
        path = os.path.join(dest, c["file"])
        cfg = _load(path)
        cfg.update(TINY_CONFIG)
        cfg["lanns"].update(TINY_LANNS)
        _dump(cfg, path)
    for w in spec["workloads"]:
        path = os.path.join(dest, "bench", "traffic", f"{w['traffic']}.json")
        traffic = _load(path)
        traffic.update(TINY_TRAFFIC[traffic["entry"]])
        _dump(traffic, path)
    return dest


def workloads(root: str) -> list:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]
