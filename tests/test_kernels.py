"""Pallas kernel vs pure-jnp oracle: shape/dtype sweeps in interpret mode.

The fp32 scan takes the binned path (bin minima, select, refine) above
k * BIN_ROWS corpus rows and the direct path at or below; the sweep runs
both, and the layouts below try to break the binned path's exactness."""

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.distance_topk import (
    BIN_ROWS,
    BLOCK_Q_MAX,
    bitonic_sort_pairs,
)
from repro.quant import quantize_q8


def _check(B, N, D, k, metric, dtype=np.float32, seed=0):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, D)).astype(dtype)
    x = rng.standard_normal((N, D)).astype(dtype)
    d_k, i_k = ops.distance_topk(q, x, k, metric, backend="pallas_interpret")
    k_eff = min(k, N)
    d_r, i_r = ref.distance_topk_ref(jnp.asarray(q), jnp.asarray(x), k_eff, metric)
    if k_eff < k:  # oracle padded to k with (inf, -1)
        d_r = jnp.concatenate(
            [d_r, jnp.full((B, k - k_eff), jnp.inf, d_r.dtype)], 1
        )
        i_r = jnp.concatenate(
            [i_r, jnp.full((B, k - k_eff), -1, i_r.dtype)], 1
        )
    d_k, i_k, d_r, i_r = map(np.asarray, (d_k, i_k, d_r, i_r))
    fin = np.isfinite(d_r)
    assert np.allclose(d_k[fin], d_r[fin], rtol=3e-4, atol=3e-4), (
        metric, np.abs(d_k - d_r)[fin].max()
    )
    # discrete-boundary metric: ids compared as sets per row (ties may swap)
    for rk, rr, f in zip(i_k, i_r, fin):
        sk, sr = set(rk[f].tolist()), set(rr[f].tolist())
        assert len(sk & sr) >= len(sr) - 1  # allow one tie swap


# sweep: dims from tiny/odd to SIFT/GIST-like, k below/at/above lane width
SWEEP = [
    (1, 100, 8, 5, "l2"),
    (5, 1000, 32, 10, "l2"),
    (8, 700, 50, 100, "l2"),     # People-dataset dims
    (3, 513, 128, 7, "ip"),      # SIFT dims, odd N
    (4, 300, 20, 5, "cos"),
    (2, 2048, 960, 64, "l2"),    # GIST dims
    (2, 64, 8, 100, "l2"),       # k > N
    (9, 255, 2048, 128, "ip"),   # NearDupe dims, k == lane width
    (5, 20_000, 50, 100, "l2"),  # N > k * BIN_ROWS: binned at the cell's k
    (3, 9_000, 128, 7, "ip"),
    (4, 5_000, 24, 20, "cos"),
]


@pytest.mark.parametrize("B,N,D,k,metric", SWEEP)
def test_kernel_matches_oracle(B, N, D, k, metric):
    _check(B, N, D, k, metric)


def _exact(q, x, k, metric="l2"):
    """float64 distances of every row, and the k-th least of each query."""
    q, x = q.astype(np.float64), x.astype(np.float64)
    if metric == "l2":
        d = ((q[:, None, :] - x[None, :, :]) ** 2).sum(-1)
    else:
        d = -(q @ x.T)
    return d, np.sort(d, axis=1)[:, k - 1]


def _layout(case, rng):
    """(q, x, k, n_valid) laid out against the binned path."""
    B, N, D = 4, 4096, 16
    q = rng.standard_normal((B, D)).astype(np.float32)
    x = rng.standard_normal((N, D)).astype(np.float32) * 4.0
    if case == "one_bin":  # every query's k nearest rows share one bin
        k = BIN_ROWS
        x[37 * BIN_ROWS:38 * BIN_ROWS] = q[0] + 1e-2 * rng.standard_normal(
            (BIN_ROWS, D)).astype(np.float32)
        return q, x, k, N
    if case == "ties":  # each row repeated 8 times: ties at every rank
        x = np.repeat(x[: N // 8], 8, axis=0)
        return q, x, 30, N
    # padded rows past n_valid nearer than every real row
    nv = N - 300
    x[nv:] = q[0]
    return q, x, 25, nv


@pytest.mark.parametrize("case", ["one_bin", "ties", "padded_nearer"])
def test_binned_path_exact_on_adversarial_layouts(case):
    """The binned path returns k rows whose float64 distances are the
    true k least, for each query: only the ids of tied rows may differ."""
    q, x, k, nv = _layout(case, np.random.default_rng(11))
    assert ops.scan_path(x.shape[0], k, "pallas_interpret") == "binned"
    d, i = map(np.asarray, ops.distance_topk(
        q, x, k, "l2", backend="pallas_interpret", n_valid=nv))
    full, kth = _exact(q, x[:nv], k)
    for b in range(len(q)):
        assert len(set(i[b].tolist())) == k and i[b].min() >= 0
        assert i[b].max() < nv
        true = full[b, i[b]]  # float64 distances of the returned ids
        assert np.allclose(d[b], true, rtol=1e-4, atol=1e-3)
        assert np.allclose(np.sort(true), np.sort(full[b])[:k],
                           rtol=1e-9, atol=1e-9)
        assert true.max() <= kth[b]


@pytest.mark.parametrize("layout", ["flat", "slab"])
@pytest.mark.parametrize(
    "N,k,metric,backend",
    [(5_120, 20, "l2", "pallas_interpret"), (5_120, 20, "cos",
     "pallas_interpret"), (256, 20, "l2", "pallas_interpret"),
     (5_120, 20, "l2", "jnp")],
    ids=["binned", "binned-cos", "direct", "blocked"],
)
def test_flat_rows_match_2d(N, k, metric, backend, layout):
    """Rows handed over in another shape, flattened or as the 128-wide
    slabs the scan engine uploads, give the answers of the same rows in
    2-D, on every path."""
    rng = np.random.default_rng(8)
    q = rng.standard_normal((6, 50)).astype(np.float32)
    x = rng.standard_normal((N, 50)).astype(np.float32)
    kw = dict(backend=backend, n_valid=N - 7)
    d2, i2 = ops.distance_topk(q, x, k, metric, **kw)
    shape = (-1,) if layout == "flat" else (-1, 128)
    d1, i1 = ops.distance_topk(q, x.reshape(shape), k, metric, **kw)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    assert np.array_equal(np.asarray(d1), np.asarray(d2))


def test_binned_path_tiles_a_large_batch():
    """A batch of more than one query tile (BLOCK_Q_MAX) is ranked a tile
    at a time, with each query's answers those it gets alone."""
    rng = np.random.default_rng(12)
    q = rng.standard_normal((BLOCK_Q_MAX + 44, 16)).astype(np.float32)
    x = rng.standard_normal((4_000, 16)).astype(np.float32)
    assert ops.scan_path(len(x), 10, "pallas_interpret") == "binned"
    d, i = map(np.asarray, ops.distance_topk(
        q, x, 10, "l2", backend="pallas_interpret"))
    assert d.shape == i.shape == (len(q), 10)
    for rows in (slice(0, 3), slice(BLOCK_Q_MAX - 1, BLOCK_Q_MAX + 2),
                 slice(len(q) - 3, len(q))):
        d1, i1 = map(np.asarray, ops.distance_topk(
            q[rows], x, 10, "l2", backend="pallas_interpret"))
        assert np.array_equal(i[rows], i1)
        assert np.allclose(d[rows], d1, rtol=1e-6, atol=1e-6)
    full, kth = _exact(q, x, 10)
    assert np.all(full[np.arange(len(q))[:, None], i].max(1) <= kth + 1e-4)


def test_scan_path_rule():
    """binned above k * BIN_ROWS rows, direct at or below; the jnp backend
    and k > 256 take the blocked scan."""
    path = ops.scan_path
    for k in (1, 7, 100, 200, 256):
        assert path(k * BIN_ROWS, k, "pallas_interpret") == "direct"
        assert path(k * BIN_ROWS + 1, k, "pallas_interpret") == "binned"
        assert path(1 << 20, k, "jnp") == "blocked"
    assert path(1 << 20, 257, "pallas_interpret") == "blocked"
    assert path(64, 100, "pallas_interpret") == "direct"  # k > N


def test_kernel_bf16_inputs():
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((4, 64)), jnp.bfloat16)
    x = jnp.asarray(rng.standard_normal((500, 64)), jnp.bfloat16)
    d_k, i_k = ops.distance_topk(q, x, 10, "l2", backend="pallas_interpret")
    d_r, i_r = ref.distance_topk_ref(
        q.astype(jnp.float32), x.astype(jnp.float32), 10, "l2"
    )
    # bf16 inputs upcast in-kernel: distances close at bf16 resolution
    assert np.allclose(np.asarray(d_k), np.asarray(d_r), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("q8", [False, True], ids=["f32", "q8"])
@pytest.mark.parametrize(
    "backend,err", [("pallas", RuntimeError), ("mosaic", ValueError)]
)
def test_backend_off_tpu_raises(monkeypatch, q8, backend, err):
    """'pallas' compiles the kernel for the chip: off a TPU it raises
    instead of quietly running the interpreter; unknown names raise too."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: False)
    rng = np.random.default_rng(6)
    q = rng.standard_normal((2, 16)).astype(np.float32)
    x = rng.standard_normal((40, 16)).astype(np.float32)
    with pytest.raises(err):
        if q8:
            ops.distance_topk_q8(q, quantize_q8(x), 5, backend=backend)
        else:
            ops.distance_topk(q, x, 5, backend=backend)


def test_blocked_jnp_path_matches_oracle():
    rng = np.random.default_rng(4)
    q = rng.standard_normal((16, 48)).astype(np.float32)
    x = rng.standard_normal((5000, 48)).astype(np.float32)
    d_b, i_b = ops.distance_topk(q, x, 20, "l2", backend="jnp")
    d_r, i_r = ref.distance_topk_ref(jnp.asarray(q), jnp.asarray(x), 20, "l2")
    assert np.allclose(np.asarray(d_b), np.asarray(d_r), rtol=1e-5)
    assert np.array_equal(np.asarray(i_b), np.asarray(i_r))


@settings(max_examples=25, deadline=None)
@given(
    st.integers(min_value=0, max_value=7).map(lambda e: 2 ** (e + 2)),  # P: 4..512
    st.integers(min_value=0, max_value=10_000),
)
def test_property_bitonic_sorts(P, seed):
    rng = np.random.default_rng(seed)
    d = jnp.asarray(rng.standard_normal((2, P)).astype(np.float32))
    i = jnp.asarray(rng.integers(0, 10 * P, (2, P)).astype(np.int32))
    sd, si = bitonic_sort_pairs(d, i)
    sd, si = np.asarray(sd), np.asarray(si)
    assert np.all(np.diff(sd, axis=1) >= 0), "ascending"
    # permutation check: same multiset of (dist, id) pairs
    for r in range(2):
        got = sorted(zip(sd[r].tolist(), si[r].tolist()))
        want = sorted(zip(np.asarray(d)[r].tolist(), np.asarray(i)[r].tolist()))
        assert got == want


def test_bitonic_with_inf_padding():
    d = jnp.asarray([[2.0, np.inf, 1.0, np.inf]])
    i = jnp.asarray([[5, -1, 9, -1]], dtype=jnp.int32)
    sd, si = bitonic_sort_pairs(d, i)
    assert np.asarray(si)[0, :2].tolist() == [9, 5]
