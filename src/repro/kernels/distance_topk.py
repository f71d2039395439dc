"""Exact fp32 distance + top-k: Pallas bin minima, then select and refine.

This is the compute hot spot of LANNS serving (DESIGN.md §2, §6): scoring a
query tile against a corpus segment is a (TN, d) x (d, TQ) matmul on the
MXU.  The paper names these "<query, document> distance comparisons" as
where "most of the search time is spent" (§7).  Ranking the scores is done
in three steps, none of which sorts inside a kernel:

1. **Bin minima** (``bin_minima_pallas``, Pallas).  Each grid step scores
   one block of rows against the whole query tile at ``Precision.HIGHEST``
   (``||x||^2 - 2 q.x`` for l2, ``-q.x`` for ip; rows ``>= n_valid`` score
   +inf) and writes, for every query, the minimum of each bin of
   ``BIN_ROWS`` adjacent rows: one elementwise ``min`` a score.  Scores lie
   rows-by-queries, so a bin is a group of sublanes.  A query tile is the
   padded batch up to BLOCK_Q_MAX queries, so each block of rows is read
   from HBM once a tile.  Out: (N / BIN_ROWS, tile) minima.
2. **Select** (``select_refine``).  For each query, the k bins with the
   smallest minima, by ``lax.top_k``; over many bins the same argument is
   applied one level up first (bins of ``FAN_IN`` bins, then the k
   best super-bins' bins), reducing the kernel's layout in place.
3. **Refine** (``select_refine``).  Gather the k * BIN_ROWS rows of the
   chosen bins, score them with the same formula and precision, and take
   their ``lax.top_k``.

``binned_topk`` runs the three steps one query tile at a time, so its
memory is bounded by the tile and the corpus, not by the batch.

Why it is exact: let tau be the k-th smallest bin minimum.  The k chosen
bins each hold a row at distance <= tau, so the true k-th distance is
<= tau.  Every true top-k row r has d_r <= tau, so its bin's minimum is
<= d_r <= tau and the bin is among the chosen k.  The answers are the true
top-k rows up to fp32 rounding at the k-th place: the kernel's scores and
the refine's are the same formula at the same precision but different
code, so they may differ in the last bit, and a row within that of the
k-th distance, like a tie there, may give way to another.

``direct_topk`` scores every row and takes one ``lax.top_k``: the path for
partitions of at most k * BIN_ROWS rows, where bins would save nothing.
``kernels/ops.py`` chooses the path from the shapes (``scan_path``).

``bitonic_sort_pairs`` is the compare/select sorting network of the q8
kernel (``distance_topk_q8.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128  # TPU lane width
BIN_ROWS = 16  # rows a bin: two sublane tiles of f32
FAN_IN = 16  # bins a super-bin in the select step
BLOCK_Q_MAX = 256  # queries a tile
REFINE_BYTES = 1 << 28  # gathered f32 rows a refine chunk
_HIGHEST = jax.lax.Precision.HIGHEST
#: query scale of the score: lower is better, and l2's per-query ||q||^2
#: is added back by the caller
_Q_SCALE = {"l2": -2.0, "ip": -1.0}


def _log2(n: int) -> int:
    l = n.bit_length() - 1
    if (1 << l) != n:
        raise ValueError(f"{n} is not a power of two")
    return l


@jax.jit  # pltpu.roll has no eager rule: callers outside a kernel trace it
def bitonic_sort_pairs(d: jnp.ndarray, i: jnp.ndarray):
    """Ascending bitonic sort of (dist, id) pairs along the last axis.

    Last axis length must be a power of two.  Every value keeps the input
    shape: a compare-exchange at partner distance ``j`` fetches the partner
    with two lane rotations (by -j and +j) and picks with iota masks, so the
    network lowers inside a Mosaic kernel (no reshape of the lane axis, no
    gather, no sort primitive).  A swap happens only on a strict comparison,
    so ties keep their ids in place.  O(P log^2 P) compare-exchanges.
    """
    P = d.shape[-1]
    LP = _log2(P)
    axis = d.ndim - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, d.shape, axis)
    for s in range(1, LP + 1):  # stage: sorted runs of length 2**s
        for t in range(s - 1, -1, -1):  # substage: partner distance 2**t
            j = 1 << t
            lower = (lane & j) == 0  # this lane holds the pair's first slot
            # roll(x, P - j)[l] == x[l + j]; roll(x, j)[l] == x[l - j]
            pd = jnp.where(
                lower, pltpu.roll(d, P - j, axis), pltpu.roll(d, j, axis)
            )
            pi = jnp.where(
                lower, pltpu.roll(i, P - j, axis), pltpu.roll(i, j, axis)
            )
            # a run is ascending iff bit ``s`` of the lane index is 0 (the
            # final merge sorts the whole row ascending).  Its lower slot
            # keeps the smaller distance, a descending run's upper slot does:
            # keep_min iff bits t and s agree.  Bit arithmetic on int32, as
            # Mosaic cannot compare two boolean vectors.
            if s < LP:
                keep_min = (((lane >> t) ^ (lane >> s)) & 1) == 0
            else:
                keep_min = lower
            take = (keep_min & (d > pd)) | (~keep_min & (d < pd))
            d = jnp.where(take, pd, d)
            i = jnp.where(take, pi, i)
    return d, i


def _bin_minima_kernel(
    nv_ref,  # (1,)        SMEM — number of real corpus rows
    q_ref,  # (TQ, D)      VMEM
    x_ref,  # (TN, D)      VMEM
    out_ref,  # (TN // BIN_ROWS, TQ)
    *,
    block_n: int,
    metric: str,
):
    x = x_ref[...].astype(jnp.float32)
    q = q_ref[...].astype(jnp.float32) * _Q_SCALE[metric]
    # full f32 contraction: the MXU's default single bf16 pass would cost
    # recall against an exact brute force
    s = jax.lax.dot_general(
        x, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=_HIGHEST,
    )  # (TN, TQ)
    if metric == "l2":
        s = jnp.sum(x * x, axis=1, keepdims=True) + s
    row = pl.program_id(1) * block_n + jax.lax.broadcasted_iota(
        jnp.int32, (block_n, 1), 0
    )
    s = jnp.where(row < nv_ref[0], s, jnp.inf)
    tn, tq = s.shape
    out_ref[...] = jnp.min(s.reshape(tn // BIN_ROWS, BIN_ROWS, tq), axis=1)


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_n", "metric", "interpret")
)
def bin_minima_pallas(
    q: jnp.ndarray,
    x: jnp.ndarray,
    n_valid,
    *,
    block_q: int,
    block_n: int,
    metric: str,
    interpret: bool = False,
):
    """Raw kernel launch.  q (B, D) with B % block_q == 0; x (N, D) with
    N % block_n == 0; D and block_q lane multiples; block_n a multiple of
    8 * BIN_ROWS.  ``n_valid`` (rows >= it score +inf) is a traced scalar
    that reaches the kernel through SMEM, so every partition padded to one
    shape bucket shares one compiled kernel.  Returns (N // BIN_ROWS, B):
    entry (j, b) is the least score of query b over rows
    [j * BIN_ROWS, (j + 1) * BIN_ROWS)."""
    B, D = q.shape
    N = x.shape[0]
    assert B % block_q == 0 and N % block_n == 0
    assert block_n % (8 * BIN_ROWS) == 0 and D % LANE == 0
    kernel = functools.partial(
        _bin_minima_kernel, block_n=block_n, metric=metric
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B // block_q, N // block_n),
        in_specs=[
            pl.BlockSpec((block_q, D), lambda iq, i_n, nv: (iq, 0)),
            pl.BlockSpec((block_n, D), lambda iq, i_n, nv: (i_n, 0)),
        ],
        out_specs=pl.BlockSpec(
            (block_n // BIN_ROWS, block_q), lambda iq, i_n, nv: (i_n, iq)
        ),
    )
    nv = jnp.asarray(n_valid, jnp.int32).reshape(1)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N // BIN_ROWS, B), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
    )(nv, q, x)


def _scores(q: jnp.ndarray, x: jnp.ndarray, metric: str) -> jnp.ndarray:
    """The kernel's score outside it: ``||x||^2 - 2 q.x`` (l2) or ``-q.x``
    (ip) at ``Precision.HIGHEST``.  ``x`` is (N, D), rows shared by every
    query (out (B, N)), or (B, M, D), rows of each query's own (out
    (B, M))."""
    spec = "nd,bd->bn" if x.ndim == 2 else "bmd,bd->bm"
    s = jnp.einsum(
        spec, x, q * _Q_SCALE[metric], precision=_HIGHEST,
        preferred_element_type=jnp.float32,
    )
    if metric == "l2":
        xn = jnp.sum(x * x, axis=-1)
        s = (xn[None, :] if x.ndim == 2 else xn) + s
    return s


def _select_bins(minima: jnp.ndarray, k: int) -> jnp.ndarray:
    """Ids (B, k) of the k least entries of each column of ``minima``
    (M, B), the kernel's layout.

    Over more than k * FAN_IN entries the bin argument is applied to
    ``minima`` itself: the k least of the minima of groups of FAN_IN hold
    the k least entries, so only their k * FAN_IN entries are ranked.  The
    groups are reduced along M, so only the last level, of at most
    k * FAN_IN entries a query, is laid out queries-first for ``top_k``."""
    M, B = minima.shape
    if M <= k * FAN_IN or M % FAN_IN:
        return jax.lax.top_k(-minima.T, k)[1]
    groups = minima.reshape(M // FAN_IN, FAN_IN, B)
    sup = _select_bins(jnp.min(groups, axis=1), k)  # (B, k)
    query = jax.lax.broadcasted_iota(jnp.int32, (B, 1), 0)
    chosen = groups[sup, :, query]  # (B, k, FAN_IN)
    _, j = jax.lax.top_k(-chosen.reshape(B, -1), k)
    cand = sup[:, :, None] * FAN_IN + jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, FAN_IN), 2
    )
    return jnp.take_along_axis(cand.reshape(B, -1), j, axis=1)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def select_refine(q, x, minima, n_valid, *, k: int, metric: str):
    """Steps 2 and 3 after ``bin_minima_pallas(q, x, n_valid)``: the k bins
    of least minimum of each query, then the exact top-k of their rows.
    Returns (B, k) scores (ascending; l2 without ``||q||^2``) and row ids;
    +inf where fewer than k rows are valid.  Queries are refined a chunk at
    a time, so the gathered rows take at most REFINE_BYTES."""
    B, D = q.shape
    bins = _select_bins(minima, k)  # (B, k)
    groups = x.reshape(minima.shape[0], BIN_ROWS, D)
    offsets = jax.lax.broadcasted_iota(jnp.int32, (1, BIN_ROWS), 1)

    def refine(args):  # one query
        qb, bb = args
        rows = groups[bb].reshape(1, k * BIN_ROWS, D)
        ids = (bb[:, None] * BIN_ROWS + offsets).reshape(k * BIN_ROWS)
        s = jnp.where(ids < n_valid, _scores(qb[None], rows, metric)[0],
                      jnp.inf)
        neg, j = jax.lax.top_k(-s, k)
        return -neg, ids[j]

    per_query = k * BIN_ROWS * D * 4
    chunk = 1 << max(0, (REFINE_BYTES // per_query).bit_length() - 1)
    return jax.lax.map(refine, (q, bins), batch_size=chunk)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def direct_topk(q, x, n_valid, *, k: int, metric: str):
    """Score every row of ``x`` (any shape of N * D elements, rows in
    order) for each query of ``q`` (B, D) and take the top k (k <= N).
    Same returns as ``select_refine``."""
    x = x.reshape(-1, q.shape[1])
    s = _scores(q, x, metric)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, x.shape[0]), 1)
    neg, ids = jax.lax.top_k(-jnp.where(col < n_valid, s, jnp.inf), k)
    return -neg, ids


def block_rows(d_pad: int, n: int) -> int:
    """Rows a grid step of ``bin_minima_pallas``: about 1 MiB of f32 rows
    of width ``d_pad``, no more than ``n`` needs, a multiple of 128."""
    cap = min(2048, max(LANE, (1 << 18) // d_pad))
    return min(cap, -(-n // LANE) * LANE)


@functools.partial(jax.jit, static_argnames=("k", "metric", "interpret"))
def binned_topk(q, x, n_valid, *, k: int, metric: str,
                interpret: bool = False):
    """Steps 1 to 3 for f32 ``q`` (B, D) against the rows of ``x``, any
    shape of N * D elements with the rows in order (N > k * BIN_ROWS).
    Lays the rows out as (N, D) and pads them to the block and D to the
    lane width once, then runs ``bin_minima_pallas`` and ``select_refine``
    one tile of at most BLOCK_Q_MAX queries at a time, so the minima and
    the gathered rows take memory bounded by the tile, whatever B is.
    Same returns as ``select_refine``."""
    B, D = q.shape
    x = x.reshape(-1, D)
    N = x.shape[0]
    d_pad = -(-D // LANE) * LANE
    block_q = min(BLOCK_Q_MAX, -(-B // LANE) * LANE)
    block_n = block_rows(d_pad, N)
    tiles = -(-B // block_q)
    qp = jnp.pad(q, ((0, tiles * block_q - B), (0, d_pad - D)))
    xp = jnp.pad(x, ((0, -(-N // block_n) * block_n - N), (0, d_pad - D)))

    def tile(qt):
        minima = bin_minima_pallas(
            qt, xp, n_valid, block_q=block_q, block_n=block_n,
            metric=metric, interpret=interpret,
        )
        return select_refine(qt, xp, minima, n_valid, k=k, metric=metric)

    d, i = jax.lax.map(tile, qp.reshape(tiles, block_q, d_pad))
    return d.reshape(-1, k)[:B], i.reshape(-1, k)[:B]
