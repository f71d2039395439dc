"""Pallas TPU kernel: fused int8 distance + streaming top-k.

The quantized counterpart of the fp32 scan in ``distance_topk.py`` — stage 1
of the two-stage (quantized scan -> exact re-rank) serving path.  Unlike
the fp32 scan it keeps a running top-k in the kernel.  Per grid step:

  1. dots = q_codes @ x_codes^T          (int8 x int8 -> int32 on the MXU)
  2. scores = n2 - 2 * q_scale * dots    (one fp32 rescale; 'ip' drops n2)
  3. merge(running_topk, block scores)   (bitonic network, ``bitonic_sort_pairs``)

Inputs are the artifacts of ``repro.quant.codec``: the corpus as int8
``codes`` with the per-dimension scales already FOLDED INTO THE QUERY
(``quantize_queries_q8``), so the kernel sees one fp32 scale per query row
plus a per-row fp32 norm correction for l2.  Int8 halves-again the VMEM/HBM
traffic of the bf16 path and runs the contraction at the MXU's int8 rate;
the fp32 work is one rank-1 rescale per (TQ, TN) tile.

The int32 -> fp32 rescale is exact for D <= 1040 (sums stay under 2^24), so
the blocked-jnp fallback in ``ref.distance_topk_q8_blocked`` reproduces
these scores bit-for-bit — asserted by tests/test_quant.py.

Constraints: k <= K_PAD (256), block sizes lane multiples, D padded to a
lane multiple by ops.py — zero padding is exact for the integer dot.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.distance_topk import bitonic_sort_pairs


def _distance_topk_q8_kernel(
    nv_ref,  # (1,)        i32   SMEM — number of real corpus rows
    q_ref,  # (TQ, D)      int8  VMEM
    x_ref,  # (TN, D)      int8  VMEM
    qs_ref,  # (TQ, 1)     f32   VMEM — per-query rescale
    n2_ref,  # (1, TN)     f32   VMEM — per-row dequantized ||x||^2
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

    # int8 x int8 -> int32: the MXU-native contraction; fp32 enters only in
    # the rank-1 rescale below.
    dots = jax.lax.dot_general(
        q_ref[...],
        x_ref[...],
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # (TQ, TN) exact
    qx = dots.astype(jnp.float32) * qs_ref[...]  # (TQ, TN) * (TQ, 1)
    if metric == "l2":
        scores = n2_ref[...] - 2.0 * qx  # ||q||^2 added by the wrapper
    else:  # ip (cos is ip over pre-normalized inputs)
        scores = -qx

    gid = in_ * block_n + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_n), 1
    )
    valid = gid < nv_ref[0]
    scores = jnp.where(valid, scores, jnp.inf)
    gids = jnp.broadcast_to(gid, scores.shape)
    gids = jnp.where(valid, gids, -1)

    cat_d = jnp.concatenate([run_d[...], scores], axis=-1)
    cat_i = jnp.concatenate([run_i[...], gids], axis=-1)
    P = cat_d.shape[-1]
    P2 = 1 << (P - 1).bit_length()
    if P2 != P:
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
def distance_topk_q8_pallas(
    q_codes: jnp.ndarray,  # (B, D) int8 — scales folded, per-query quantized
    x_codes: jnp.ndarray,  # (N, D) int8
    q_scale: jnp.ndarray,  # (B, 1) f32
    norms2: jnp.ndarray,  # (1, N) f32 (+inf on padding rows)
    n_valid,  # traced scalar, SMEM (as in ``bin_minima_pallas``)
    *,
    k_pad: int,
    block_q: int,
    block_n: int,
    metric: str,
    interpret: bool = False,
):
    """Raw kernel launch: B % block_q == 0, N % block_n == 0, D a lane
    multiple, k_pad a power of two, block sizes lane multiples.  Returns
    (B, k_pad) ascending quantized scores + global ids."""
    B, D = q_codes.shape
    N = x_codes.shape[0]
    assert B % block_q == 0 and N % block_n == 0
    nq, nn = B // block_q, N // block_n
    kernel = functools.partial(
        _distance_topk_q8_kernel,
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
            pl.BlockSpec((block_q, 1), lambda iq, in_, nv: (iq, 0)),
            pl.BlockSpec((1, block_n), lambda iq, in_, nv: (0, in_)),
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
    )(nv, q_codes, x_codes, q_scale, norms2)
