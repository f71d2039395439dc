"""Pallas TPU kernel: fused blocked distance + streaming top-k.

This is the compute hot spot of LANNS serving (DESIGN.md §2, §6): scoring a
query tile against a corpus segment is a (TQ, d) x (d, TN) matmul on the MXU,
and the top-k selection is fused into the same kernel so candidate scores
never round-trip to HBM.  The kernel is the TPU-native replacement for the
"<query, document> distance comparisons" that the paper identifies as where
"most of the search time is spent" (§7).

Grid/tiling
-----------
grid = (num_q_tiles, num_n_blocks); the N axis is the innermost (sequential on
TPU) grid dimension, and a VMEM scratch carries the running per-query top-k
(dists + global ids) across N blocks — the same accumulator pattern as
flash-attention.  Per grid step:

  1. scores = x_norm - 2 * q @ x_blk^T           (MXU matmul, f32 accum)
  2. merge(running_topk, block scores)           (bitonic network, VPU)
  3. last block: write (TQ, K_PAD) results out

The merge sorts the concatenated [K_PAD running | TN block] row of each query
with a bitonic network expressed ONLY as lane rotations + elementwise select
at the row's own (TQ, P) shape, because Mosaic does not lower lax.top_k/sort
inside kernels, nor a reshape that splits the lane axis; rotations map to
the TPU's lane shuffles and are exactly emulated in interpret mode on CPU.

Constraints: k <= K_PAD (=256 default); d padded to a lane multiple by ops.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_LANES = 128  # TPU lane width; block sizes are multiples of this


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


def _distance_topk_kernel(
    nv_ref,  # (1,)         SMEM — number of real corpus rows
    q_ref,  # (TQ, D)       VMEM
    x_ref,  # (TN, D)       VMEM
    out_d_ref,  # (TQ, K_PAD)
    out_i_ref,  # (TQ, K_PAD)
    run_d,  # scratch (TQ, K_PAD) f32
    run_i,  # scratch (TQ, K_PAD) i32
    *,
    k_pad: int,
    block_n: int,
    metric: str,
):
    in_ = pl.program_id(1)
    nn = pl.num_programs(1)

    @pl.when(in_ == 0)
    def _init():
        run_d[...] = jnp.full(run_d.shape, jnp.inf, run_d.dtype)
        run_i[...] = jnp.full(run_i.shape, -1, run_i.dtype)

    q = q_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    # scores: lower is better.  l2 drops the per-query ||q||^2 constant
    # (added back by the ops.py wrapper) so the MXU does one matmul + one
    # rank-1 broadcast add.
    # full f32 contraction: the MXU's default single bf16 pass would cost
    # recall against an exact brute force
    qx = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # (TQ, TN)
    if metric == "l2":
        x_norm = jnp.sum(x * x, axis=-1)  # (TN,)
        scores = x_norm[None, :] - 2.0 * qx
    else:  # ip (cos is ip over pre-normalized inputs)
        scores = -qx

    gid = in_ * block_n + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_n), 1
    )  # (1, TN)
    valid = gid < nv_ref[0]
    scores = jnp.where(valid, scores, jnp.inf)
    gids = jnp.broadcast_to(gid, scores.shape)
    gids = jnp.where(valid, gids, -1)

    cat_d = jnp.concatenate([run_d[...], scores], axis=-1)  # (TQ, K_PAD+TN)
    cat_i = jnp.concatenate([run_i[...], gids], axis=-1)
    P = cat_d.shape[-1]
    P2 = 1 << (P - 1).bit_length()
    if P2 != P:  # bitonic needs a power of two; pad with +inf sentinels
        pad = ((0, 0), (0, P2 - P))
        cat_d = jnp.pad(cat_d, pad, constant_values=jnp.inf)
        cat_i = jnp.pad(cat_i, pad, constant_values=-1)
    sd, si = bitonic_sort_pairs(cat_d, cat_i)
    run_d[...] = sd[:, :k_pad]
    run_i[...] = si[:, :k_pad]

    @pl.when(in_ == nn - 1)
    def _flush():
        out_d_ref[...] = run_d[...]
        out_i_ref[...] = run_i[...]


@functools.partial(
    jax.jit,
    static_argnames=("k_pad", "block_q", "block_n", "metric", "interpret"),
)
def distance_topk_pallas(
    q: jnp.ndarray,
    x: jnp.ndarray,
    n_valid,
    *,
    k_pad: int,
    block_q: int,
    block_n: int,
    metric: str,
    interpret: bool = False,
):
    """Raw kernel launch. q (B, D) with B % block_q == 0; x (N, D) with
    N % block_n == 0; D a lane multiple; k_pad a power of two; block sizes
    lane multiples.  ``n_valid`` (rows >= it are ignored) is a traced
    scalar that reaches the kernel through SMEM, so every partition padded
    to one shape bucket shares one compiled kernel.  Returns (B, k_pad)
    dists (ascending) + global ids."""
    B, D = q.shape
    N = x.shape[0]
    assert B % block_q == 0 and N % block_n == 0
    nq, nn = B // block_q, N // block_n
    kernel = functools.partial(
        _distance_topk_kernel,
        k_pad=k_pad,
        block_n=block_n,
        metric=metric,
    )
    out_shape = (
        jax.ShapeDtypeStruct((B, k_pad), jnp.float32),
        jax.ShapeDtypeStruct((B, k_pad), jnp.int32),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nq, nn),
        in_specs=[
            pl.BlockSpec((block_q, D), lambda iq, in_, nv: (iq, 0)),
            pl.BlockSpec((block_n, D), lambda iq, in_, nv: (in_, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k_pad), lambda iq, in_, nv: (iq, 0)),
            pl.BlockSpec((block_q, k_pad), lambda iq, in_, nv: (iq, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, k_pad), jnp.float32),
            pltpu.VMEM((block_q, k_pad), jnp.int32),
        ],
    )
    nv = jnp.asarray(n_valid, jnp.int32).reshape(1)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(nv, q, x)
