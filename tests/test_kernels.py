"""Pallas kernel vs pure-jnp oracle: shape/dtype sweeps in interpret mode."""

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.distance_topk import bitonic_sort_pairs
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
]


@pytest.mark.parametrize("B,N,D,k,metric", SWEEP)
def test_kernel_matches_oracle(B, N, D, k, metric):
    _check(B, N, D, k, metric)


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
