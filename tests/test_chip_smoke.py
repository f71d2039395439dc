"""CPU rehearsal of chip_smoke.py's phases at a tiny size.

The phases run here exactly as on the chip — build, warm, serve through
``AsyncAnnFrontend``, score against the numpy brute force — only smaller,
and never through ``main``: on the CPU the script refuses to run and prints
no result line.  The four-chip phase is rehearsed on virtual CPU devices in
tests/test_distributed.py.
"""

import importlib.util
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_tpu(smoke, capsys):
    with pytest.raises(RuntimeError, match="needs a TPU"):
        smoke.require_tpu()
    assert smoke.main([]) == 2
    assert '"ok"' not in capsys.readouterr().out


_CACHE_PROBE = """
import os, jax, jax.numpy as jnp
from repro.common.utils import enable_compile_cache
d = enable_compile_cache()
assert jax.config.jax_compilation_cache_dir == d, d
assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
if os.environ.get("PROBE_COMPILE"):
    jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((8, 8))).block_until_ready()
print(d)
"""


@pytest.mark.parametrize("env_dir", [False, True], ids=["default", "env"])
def test_compile_cache_placement(tmp_path, env_dir):
    """Unset, the cache goes to <checkout>/.jax_cache; set, JAX keeps the
    directory the environment names and compiles land there."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(_PATH.parent / "src")
    want = _PATH.parent / ".jax_cache"
    if env_dir:
        env.update(JAX_COMPILATION_CACHE_DIR=str(tmp_path), PROBE_COMPILE="1")
        want = tmp_path
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], env=env, capture_output=True,
        text=True, timeout=300, check=True,
    ).stdout
    assert out.strip().splitlines()[-1] == str(want)
    if env_dir:
        assert any(p.name.endswith("-cache") for p in tmp_path.iterdir())


def test_exact_topk_matches_full_sort(smoke):
    data, queries = smoke.make_vectors(3000, 16, 8, seed=5, chunk=1024)
    assert data.shape == (3000, 16) and queries.shape == (8, 16)
    got = smoke.exact_topk(data, queries, 10, chunk=700)
    d2 = ((queries[:, None, :] - data[None, :, :]) ** 2).sum(-1)
    want = np.argsort(d2, axis=1)[:, :10]
    assert smoke.recall(got, want) == 1.0


def test_scan_phases_tiny(smoke):
    recs = list(smoke.scan_phases(20_000, 32, n_requests=96, n_check=32))
    assert [r["phase"] for r in recs] == ["scan_fp32", "scan_q8"]
    for r in recs:
        assert r["requests"] == 96 and r["batches"] >= 1
        assert r["recall_at_100"] > 0.6, r
    # the CPU serves the scan with the blocked jnp path, not the kernel
    assert recs[0]["pallas_kernel_traces"] == 0


def test_scan_phase_counts_the_kernel_where_it_runs(smoke, monkeypatch):
    """Where the scan takes the kernel's path (here through the Pallas
    interpreter), the fp32 phase counts its compiled kernel launches."""
    from repro.kernels import ops

    resolve = ops._resolve_backend
    monkeypatch.setattr(
        ops, "_resolve_backend",
        lambda b: "pallas_interpret" if b == "auto" else resolve(b))
    # 16 segments of about 2,500 rows: above 16 * k, the binned path
    rec = next(smoke.scan_phases(40_000, 32, n_requests=32, n_check=16))
    assert rec["phase"] == "scan_fp32" and rec["recall_at_100"] > 0.6
    assert rec["pallas_kernel_traces"] > 0


def test_hnsw_phases_tiny(smoke):
    recs = list(smoke.hnsw_phases(
        6_000, 16, n_requests=64, n_check=32, workers=2
    ))
    assert [r["phase"] for r in recs] == ["hnsw_fp32", "hnsw_q8"]
    for r in recs:
        assert r["build_workers"] == 2
        # 375-row segments: routing, not the beam, bounds recall here
        assert r["recall_at_100"] > 0.5, r
    # the q8 index resumes from the fp32 build's graphs
    assert recs[1]["build_s"] < recs[0]["build_s"]
