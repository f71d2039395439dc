"""The benchmark's whole run, rehearsed on the CPU at a tiny size.

Each cell of ``BENCHMARK.json`` runs through ``bench.lib.harness.run`` with
the look for a chip skipped, to a contract-shaped result; the same run with
its timed path broken underneath must come out not correct; the command
itself must refuse to run without a TPU.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench_tiny import REPO, tiny_root, workloads

sys.path.insert(0, REPO)

from bench import faults  # noqa: E402
from bench.lib import harness  # noqa: E402

SEED = 2**31 + 2**30 + 12345  # wider than 32 signed bits, as a check's are


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny copy; JAX's compile-cache settings are put back after."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    yield tiny_root(str(tmp_path_factory.mktemp("bench")))
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def _run(root, workload, trace=0, fault=None, seconds=0.5):
    import time

    return harness.run(root, workload, SEED, seconds, trace,
                       t_start=time.perf_counter(), require_chip=False,
                       fault=fault)


def _contract_shaped(res, metrics):
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert isinstance(res["correct"], bool)
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == set(metrics)
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and np.isfinite(m["value"])
    dev = res["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    assert dev["count"] == 1
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}
    json.loads(json.dumps(res))  # one JSON object, as printed


@pytest.mark.parametrize("trace", [0, 1])
def test_every_cell_runs_to_a_contract_shaped_result(root, trace):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for name in workloads(root):
        res = _run(root, name, trace)
        kind = "per_layer" if trace else "end_to_end"
        want = {m["name"] for m in spec[kind]
                if name in m.get("workloads", [name])}
        if trace:  # the CPU has no device trace: those readers stay silent
            want = {m for m in want
                    if not m.startswith(("scan_roofline", "device_idle"))}
            assert {"busy_s", "window_s"} <= set(res["device"])
            assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        _contract_shaped(res, want)
        assert res["correct"], res["checks"]


@pytest.mark.parametrize("fault", [faults.alter_one_answer,
                                   faults.drop_half_the_batch,
                                   faults.misroute])
def test_a_broken_timed_path_comes_out_not_correct(root, fault):
    for name in workloads(root):
        res = _run(root, name, fault=fault)
        assert res["correct"] is False, (name, res["checks"])


def test_the_command_refuses_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench", "run.py"), "--workload",
         "people50d.offline", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "TPU" in proc.stderr
