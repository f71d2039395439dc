"""The plain reference: exact nearest neighbours by brute force.

It imports nothing of the program under test and takes nothing it made.
The corpus is drawn again on the device, chunk by chunk, from the seed
(``data.Mixture``), every row is scored against the sampled queries, and a
running top-k is kept; the distances of the ids an answer returned are
recomputed in float64 on the host.  Distances follow the program's
convention, lower is better: squared l2, ``-<q, x>`` for ip and
``-cos(q, x)`` for cos.

``precision="bf16"`` is the control: the same search computed in bfloat16,
the step below the float32 that the configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.data import Mixture

METRICS = ("l2", "ip", "cos")


def _prep(x, metric, dtype):
    x = x.astype(dtype)
    if metric == "cos":
        norm = jnp.sqrt(jnp.sum(x.astype(jnp.float32) ** 2, -1, keepdims=True))
        x = (x / jnp.maximum(norm, 1e-12).astype(dtype)).astype(dtype)
    return x


@functools.partial(jax.jit, static_argnames=("k", "metric", "precision"))
def _merge_chunk(best_d, best_i, q, x, start, n, k, metric, precision):
    """Score chunk ``x`` (rows ``start...``) and merge it into the top-k."""
    dtype = jnp.bfloat16 if precision == "bf16" else jnp.float32
    prec = (jax.lax.Precision.DEFAULT if precision == "bf16"
            else jax.lax.Precision.HIGHEST)
    qx = _prep(q, metric, dtype)
    xx = _prep(x, metric, dtype)
    dots = jnp.einsum("bd,nd->bn", qx, xx, precision=prec,
                      preferred_element_type=dtype)
    if metric == "l2":
        qn = jnp.sum(qx * qx, -1, dtype=dtype)
        xn = jnp.sum(xx * xx, -1, dtype=dtype)
        dist = qn[:, None] + xn[None, :] - 2 * dots
    else:
        dist = -dots
    dist = dist.astype(jnp.float32)
    ids = start + jnp.arange(x.shape[0], dtype=jnp.int32)
    dist = jnp.where(ids[None, :] < n, dist, jnp.inf)
    kk = min(k, x.shape[0])
    neg, loc = jax.lax.top_k(-dist, kk)
    cat_d = jnp.concatenate([best_d, -neg], axis=1)
    cat_i = jnp.concatenate([best_i, ids[loc]], axis=1)
    neg, pos = jax.lax.top_k(-cat_d, k)
    return -neg, jnp.take_along_axis(cat_i, pos, axis=1)


def exact_topk(mix: Mixture, queries: np.ndarray, k: int, metric: str,
               precision: str = "f32", block: int = 256):
    """(dists, ids) of the ``k`` nearest corpus rows for each query,
    ascending.  ``block`` queries are scored at a time."""
    if metric not in METRICS:
        raise ValueError(f"metric={metric!r} — expected one of {METRICS}")
    out_d, out_i = [], []
    for s in range(0, len(queries), block):
        q = np.zeros((block, queries.shape[1]), np.float32)
        qb = queries[s: s + block]
        q[: len(qb)] = qb
        q = jnp.asarray(q)
        best_d = jnp.full((block, k), jnp.inf, jnp.float32)
        best_i = jnp.full((block, k), -1, jnp.int32)
        for c in range(mix.n_chunks):
            best_d, best_i = _merge_chunk(
                best_d, best_i, q, mix.corpus_chunk(c),
                jnp.int32(c * mix.rows), jnp.int32(mix.n), k=k,
                metric=metric, precision=precision,
            )
        out_d.append(np.asarray(best_d)[: len(qb)])
        out_i.append(np.asarray(best_i)[: len(qb)].astype(np.int64))
    return np.concatenate(out_d), np.concatenate(out_i)


def exact_distances(corpus: np.ndarray, queries: np.ndarray, ids: np.ndarray,
                    metric: str) -> np.ndarray:
    """float64 distance of each ``ids[r, j]`` row to ``queries[r]``; NaN
    where the id is not a corpus row."""
    ok = (ids >= 0) & (ids < len(corpus))
    dist = np.full(ids.shape, np.nan)
    for r in range(len(ids)):  # one query at a time: (k, d) float64 rows
        x = corpus[ids[r][ok[r]]].astype(np.float64)
        q = queries[r].astype(np.float64)
        if metric == "l2":
            d = np.sum((x - q) ** 2, axis=-1)
        else:
            d = -(x @ q)
            if metric == "cos":
                d = d / np.maximum(
                    np.linalg.norm(x, axis=-1) * np.linalg.norm(q), 1e-300
                )
        dist[r, ok[r]] = d
    return dist
