"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth that kernel tests assert against (interpret mode),
and the CPU execution path for benchmarks (interpret-mode Pallas is a Python
loop and not representative of anything).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _dot_t(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a @ b.T at full f32 precision (a TPU's default is one bf16 pass)."""
    return jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)


def distance_matrix(q: jnp.ndarray, x: jnp.ndarray, metric: str) -> jnp.ndarray:
    """(B, d) x (N, d) -> (B, N) distances, lower is better.

    l2:  true squared euclidean distance.
    ip:  negative inner product.
    cos: negative cosine similarity (inputs need not be normalized).
    """
    if metric == "l2":
        qn = jnp.sum(q * q, axis=-1, keepdims=True)
        xn = jnp.sum(x * x, axis=-1)
        return qn - 2.0 * _dot_t(q, x) + xn[None, :]
    if metric == "ip":
        return -_dot_t(q, x)
    if metric == "cos":
        qn = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
        xn = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)
        return -_dot_t(qn, xn)
    raise ValueError(metric)


@partial(jax.jit, static_argnames=("k", "metric"))
def distance_topk_ref(q: jnp.ndarray, x: jnp.ndarray, k: int, metric: str = "l2"):
    """Oracle: full (B, N) distance matrix + lax.top_k.

    Returns (dists (B, k) ascending, ids (B, k) int32).
    """
    d = distance_matrix(q, x, metric)
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, idx.astype(jnp.int32)


@partial(jax.jit, static_argnames=("k", "metric", "block_n"))
def distance_topk_blocked(
    q: jnp.ndarray, x: jnp.ndarray, k: int, metric: str = "l2",
    block_n: int = 4096, n_valid=None,
):
    """Memory-bounded oracle: scan over N blocks carrying a running top-k.

    Semantically identical to distance_topk_ref but never materializes the
    full (B, N) matrix — this is the production CPU/brute-force path and the
    reference for the streaming behaviour of the Pallas kernel.

    ``n_valid`` (traced scalar) masks rows >= n_valid as padding, so corpora
    padded to shared pow2 size buckets share ONE compiled trace; results are
    bit-identical to scanning the unpadded corpus (padding rows score +inf
    and valid entries are untouched — matmul rows are independent).
    """
    B, dim = q.shape
    N = x.shape[0]
    nb = -(-N // block_n)
    n_pad = nb * block_n
    x_pad = jnp.pad(x, ((0, n_pad - N), (0, 0)))
    x_blocks = x_pad.reshape(nb, block_n, dim)
    nv = jnp.asarray(N if n_valid is None else n_valid, jnp.int32)

    init_d = jnp.full((B, k), jnp.inf, dtype=jnp.float32)
    init_i = jnp.full((B, k), -1, dtype=jnp.int32)

    def step(carry, inp):
        run_d, run_i = carry
        blk_idx, xb = inp
        d = distance_matrix(q, xb, metric).astype(jnp.float32)
        gid = blk_idx * block_n + jnp.arange(block_n, dtype=jnp.int32)
        valid = gid < nv
        d = jnp.where(valid[None, :], d, jnp.inf)
        cat_d = jnp.concatenate([run_d, d], axis=1)
        cat_i = jnp.concatenate(
            [run_i, jnp.broadcast_to(gid[None, :], (B, block_n))], axis=1
        )
        neg, idx = jax.lax.top_k(-cat_d, k)
        return (-neg, jnp.take_along_axis(cat_i, idx, axis=1)), None

    (out_d, out_i), _ = jax.lax.scan(
        step, (init_d, init_i), (jnp.arange(nb, dtype=jnp.int32), x_blocks)
    )
    out_i = jnp.where(jnp.isinf(out_d), -1, out_i)
    return out_d, out_i


def q8_score_matrix(
    q_codes: jnp.ndarray,  # (B, D) int8
    x_codes: jnp.ndarray,  # (N, D) int8
    q_scale: jnp.ndarray,  # (B,) f32
    norms2: jnp.ndarray,  # (N,) f32
    metric: str,
) -> jnp.ndarray:
    """(B, N) stage-1 quantized scores, lower is better — the jnp twin of the
    int8 Pallas kernel's per-tile math.  The dot runs int8 x int8 -> int32
    (exact), then ONE fp32 rescale — identical value and operation order to
    the kernel, so scores match bit-for-bit."""
    dots = jax.lax.dot_general(
        q_codes, x_codes, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    qx = dots.astype(jnp.float32) * q_scale[:, None]
    if metric == "l2":
        return norms2[None, :] - 2.0 * qx
    if metric == "ip":
        return -qx
    raise ValueError(metric)


@partial(jax.jit, static_argnames=("k", "metric", "block_n"))
def distance_topk_q8_blocked(
    q_codes: jnp.ndarray,
    x_codes: jnp.ndarray,
    q_scale: jnp.ndarray,
    norms2: jnp.ndarray,
    k: int,
    metric: str = "l2",
    block_n: int = 4096,
    n_valid=None,
):
    """Memory-bounded int8 scan: N blocks carrying a running top-k.

    Semantically identical to the streaming merge inside
    ``distance_topk_q8_pallas`` (scores are bit-equal; ties at the k
    boundary may order differently between lax.top_k and the bitonic
    network).  ``n_valid`` masks padding rows so corpora padded to shared
    shape buckets reuse one trace."""
    B = q_codes.shape[0]
    N = x_codes.shape[0]
    nb = -(-N // block_n)
    n_pad = nb * block_n
    x_pad = jnp.pad(x_codes, ((0, n_pad - N), (0, 0)))
    n2_pad = jnp.pad(norms2, (0, n_pad - N), constant_values=jnp.inf)
    x_blocks = x_pad.reshape(nb, block_n, -1)
    n2_blocks = n2_pad.reshape(nb, block_n)
    nv = jnp.asarray(N if n_valid is None else n_valid, jnp.int32)

    init_d = jnp.full((B, k), jnp.inf, dtype=jnp.float32)
    init_i = jnp.full((B, k), -1, dtype=jnp.int32)

    def step(carry, inp):
        run_d, run_i = carry
        blk_idx, xb, n2b = inp
        d = q8_score_matrix(q_codes, xb, q_scale, n2b, metric)
        gid = blk_idx * block_n + jnp.arange(block_n, dtype=jnp.int32)
        valid = gid < nv
        d = jnp.where(valid[None, :], d, jnp.inf)
        cat_d = jnp.concatenate([run_d, d], axis=1)
        cat_i = jnp.concatenate(
            [run_i, jnp.broadcast_to(gid[None, :], (B, block_n))], axis=1
        )
        neg, idx = jax.lax.top_k(-cat_d, k)
        return (-neg, jnp.take_along_axis(cat_i, idx, axis=1)), None

    (out_d, out_i), _ = jax.lax.scan(
        step,
        (init_d, init_i),
        (jnp.arange(nb, dtype=jnp.int32), x_blocks, n2_blocks),
    )
    out_i = jnp.where(jnp.isinf(out_d), -1, out_i)
    return out_d, out_i


def bitonic_topk_ref(d: jnp.ndarray, i: jnp.ndarray, k: int):
    """Oracle for the in-kernel bitonic partial sort: ascending-by-distance
    (dist, id) pairs, first k returned."""
    order = jnp.argsort(d, axis=-1)
    return (
        jnp.take_along_axis(d, order, axis=-1)[..., :k],
        jnp.take_along_axis(i, order, axis=-1)[..., :k],
    )
