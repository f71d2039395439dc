"""Compile the serving path's device programs for a described TPU v5e.

Nothing runs: each case lowers and compiles at real widths for one chip (or
the four chips) of a ``v5e:2x2`` topology that the installed TPU compiler
describes without a chip attached.  That catches what interpret mode cannot
— Mosaic layouts, tiling alignment, fast-memory limits — before a chip is
used.  The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library at a time, and every
test worker must collect the same tests.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh
from jax.sharding import SingleDeviceSharding

from repro.core.hnsw import beam_search_flat
from repro.core.lanns import LannsConfig
from repro.kernels.distance_topk import distance_topk_pallas
from repro.kernels.distance_topk_q8 import distance_topk_q8_pallas
from repro.quant.twostage import _EXACT_CAST_MAX_D, _stage1_scores
from repro.serve.retrieval import make_serve_fn


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _block_n(k_pad, block_n=256):
    # the ops.py wrapper's choice: k_pad + block_n a power of two
    block_n = max(block_n, k_pad)
    return (1 << (k_pad + block_n - 1).bit_length()) - k_pad


@pytest.mark.parametrize("q8", [False, True], ids=["f32", "q8"])
@pytest.mark.parametrize("k_pad", [128, 256])
@pytest.mark.parametrize("D", [128, 256, 2048])
def test_scan_kernel_compiles(one_chip, D, k_pad, q8):
    bq, bn = 8, _block_n(k_pad)
    B, N = 256, bn * 64
    if q8:
        args = (
            _sds((B, D), jnp.int8, one_chip),
            _sds((N, D), jnp.int8, one_chip),
            _sds((B, 1), jnp.float32, one_chip),
            _sds((1, N), jnp.float32, one_chip),
            _sds((), jnp.int32, one_chip),
        )
        fn = distance_topk_q8_pallas
    else:
        args = (
            _sds((B, D), jnp.float32, one_chip),
            _sds((N, D), jnp.float32, one_chip),
            _sds((), jnp.int32, one_chip),
        )
        fn = distance_topk_pallas
    compiled = fn.lower(
        *args, k_pad=k_pad, block_q=bq, block_n=bn, metric="l2"
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("D", [256, 2048])
def test_q8_stage1_compiles(one_chip, D):
    # one segment of a 4M-row, 16-segment corpus (quarter-pow2 bucket)
    L, n_pad = 320, 262_144
    compiled = _stage1_scores.lower(
        _sds((L, D), jnp.float32, one_chip),
        _sds((n_pad, D), jnp.int8, one_chip),
        _sds((D + n_pad,), jnp.float32, one_chip),
        -2.0,
        D <= _EXACT_CAST_MAX_D,
    ).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "q8"])
def test_beam_search_flat_compiles(one_chip, quantized):
    # 200k x 128d over 16 partitions: n_pad 16384 rows each, M=16, 4 upper
    # levels; ~3 routed lanes per query of a 256-query batch
    rows, d, M, L, T = 16 * 16_384, 128, 16, 4, 768
    arrs = {
        "vectors": _sds(
            (rows, d), jnp.int8 if quantized else jnp.float32, one_chip
        ),
        "adj0": _sds((rows, 2 * M), jnp.int32, one_chip),
        "upper_adj": _sds((L, rows, M), jnp.int32, one_chip),
    }
    if quantized:
        arrs["norms2"] = _sds((rows,), jnp.float32, one_chip)
    compiled = beam_search_flat.lower(
        arrs,
        _sds((T, d), jnp.float32, one_chip),
        _sds((T,), jnp.int32, one_chip),
        _sds((T,), jnp.int32, one_chip),
        _sds((T,), jnp.bool_, one_chip),
        k=200 if quantized else 100,
        ef=200,
        max_iters=232,
        metric="l2",
    ).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("mode", ["full", "routed"])
def test_sharded_serve_compiles_on_four_chips(topo, mode):
    # one 4M x 256d LANNS shard per chip, 16 segments, broker all_gather
    mesh = Mesh(
        np.asarray(topo.devices[:4]).reshape(1, 4), ("data", "model")
    )
    cfg = LannsConfig(
        num_shards=4, num_segments=16, segmenter="apd", engine="scan"
    )
    B, d, n_seg = 256, 256, 262_144
    serve_fn, sh = make_serve_fn(
        mesh, cfg, topk=100, mode=mode, batch_per_device=B,
        capacity_factor=1e9,
    )
    rep = sh["replicated"]
    tree = {
        "hyperplanes": _sds((15, d), jnp.float32, rep),
        "split": _sds((15,), jnp.float32, rep),
        "lo": _sds((15,), jnp.float32, rep),
        "hi": _sds((15,), jnp.float32, rep),
    }
    compiled = jax.jit(serve_fn).lower(
        _sds((B, d), jnp.float32, sh["queries"]),
        _sds((4, 16, n_seg, d), jnp.float32, sh["corpus"]),
        _sds((4, 16, n_seg), jnp.int32, sh["ids"]),
        _sds((4, 16, n_seg), jnp.float32, sh["norms"]),
        tree,
    ).compile()
    text = compiled.as_text()
    assert "all-gather" in text
    # each chip holds its own shard (a quarter of the corpus, not all of
    # it), and the program fits the chip's 16 GB of HBM
    mem = compiled.memory_analysis()
    shard_bytes = 16 * n_seg * d * 4
    assert shard_bytes < mem.argument_size_in_bytes < 1.1 * shard_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15e9
