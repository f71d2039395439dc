"""Find a cell's parts by name: the benchmark file, its configuration, its
traffic mix, its per-layer metric readers and the chip's peaks.

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own under the benchmark directory, found by the name that
``BENCHMARK.json`` gives it, so a new cell is a new entry plus new files:

    <bench>/configs/<config>.json
    <bench>/traffic/<traffic>.json
    <bench>/metrics/<metric>.py     (defines ``read(record)``)
    <bench>/peaks.json              (keyed by ``device_kind``)
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Optional

BENCH_DIR = "bench"


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload entry of ``BENCHMARK.json`` with its parts resolved."""

    name: str
    chips: int
    config: dict  # the configuration file's contents
    traffic: dict  # the traffic file's contents
    end_to_end: tuple  # metric entries this cell reports with --trace 0
    per_layer: tuple  # metric entries this cell reports with --trace 1


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, name: str) -> Cell:
    """Resolve workload ``name`` of ``<root>/BENCHMARK.json``."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(
            f"no workload {name!r} in BENCHMARK.json (have {sorted(by_name)})"
        )
    w = by_name[name]
    cfgs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = cfgs[w["config"]]
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(
        os.path.join(root, BENCH_DIR, "traffic", f"{w['traffic']}.json")
    )
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=tuple(m for m in spec["end_to_end"] if _reports(m, name)),
        per_layer=tuple(m for m in spec["per_layer"] if _reports(m, name)),
    )


def metric_reader(root: str, name: str) -> Callable[[dict], Optional[float]]:
    """The ``read`` function of ``<bench>/metrics/<name>.py``.

    Loaded by path: a metric's name may hold dots, which a module name may
    not."""
    path = os.path.join(root, BENCH_DIR, "metrics", f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path
    )
    if mod_spec is None or mod_spec.loader is None:
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def device_peaks(root: str, device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    table = load_json(os.path.join(root, BENCH_DIR, "peaks.json"))
    kinds = table["devices"]
    if device_kind not in kinds:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in peaks.json "
            f"(have {sorted(kinds)})"
        )
    return kinds[device_kind]
