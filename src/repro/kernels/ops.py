"""Jit'd public wrappers around the Pallas kernels.

``distance_topk`` is the one entry point the rest of the system uses; it
handles padding (queries to the q-tile, corpus to the n-block, feature dim to
the lane width, k to the kernel's power-of-two buffer), metric normalization,
and backend selection:

* ``auto`` on TPU: the fused Pallas kernel (distance_topk_pallas);
* ``auto`` elsewhere (the CPU test runs): the blocked-scan jnp path, which
  is semantically identical (same streaming merge) and XLA-fused;
* ``pallas`` compiles the kernel for the TPU and raises off it — a kernel
  asked for on the chip never falls back to the interpreter;
* ``pallas_interpret`` runs the same kernel through the Pallas interpreter —
  used by the kernel tests to validate the TPU code path on CPU.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.utils import next_pow2, round_up
from repro.kernels import ref
from repro.kernels.distance_topk import distance_topk_pallas
from repro.kernels.distance_topk_q8 import distance_topk_q8_pallas

LANE = 128

# Scale-safety contract (repro.analysis.scalecheck): corpora arrive padded
# to shared pow2/quarter-pow2 buckets of up to 2^25 rows; feature dims to
# 2048.  B and k are intentionally NOT declared here: the batch is bucketed
# by the callers and k ranges over the per-request knob set (bounded in
# core/lanns.py / core/plan.py where those knobs are formed).
# lanns: dims[N<=33_554_432, D<=2048]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve_backend(backend: str) -> str:
    """'auto' -> 'pallas' on TPU, 'jnp' elsewhere; 'pallas' off the TPU
    raises instead of silently interpreting the kernel."""
    if backend == "auto":
        return "pallas" if _on_tpu() else "jnp"
    if backend not in ("pallas", "pallas_interpret", "jnp"):
        raise ValueError(
            f"backend={backend!r} — expected 'auto', 'pallas', "
            "'pallas_interpret' or 'jnp'"
        )
    if backend == "pallas" and not _on_tpu():
        raise RuntimeError(
            "backend='pallas' needs a TPU (default backend is "
            f"{jax.default_backend()!r}); use 'pallas_interpret' to run the "
            "kernel through the interpreter"
        )
    return backend


# lanns: hotpath
def distance_topk(
    q,
    x,
    k: int,
    metric: str = "l2",
    *,
    block_q: int = 8,
    block_n: int = 256,
    backend: str = "auto",  # 'auto' | 'pallas' | 'pallas_interpret' | 'jnp'
    n_valid: int | None = None,
):
    """Top-k nearest rows of ``x`` for each row of ``q``.

    Returns (dists (B, k) ascending, ids (B, k) int32; id -1 where fewer than
    k valid rows exist).  For metric='l2' distances are true squared L2; for
    'ip'/'cos' they are negative (inner product / cosine similarity).

    ``n_valid``: number of real corpus rows when ``x`` is padded to a shared
    shape bucket (rows >= n_valid are ignored).  It is a traced scalar on
    both paths (the Pallas kernel reads it from SMEM), so every partition
    padded to the same bucket reuses ONE compiled trace — the point of the
    scan-engine pow2 bucketing.
    """
    backend = _resolve_backend(backend)
    q = jnp.asarray(q)
    x = jnp.asarray(x)
    B, D = q.shape
    N = x.shape[0]
    nv = N if n_valid is None else min(int(n_valid), N)
    if N == 0 or nv == 0:
        # empty corpus: nothing to rank.  The k > N recursion below would
        # otherwise bottom out calling the blocked scan with k=0 — return the
        # (inf, -1) padding directly.
        return (
            jnp.full((B, k), jnp.inf, jnp.float32),
            jnp.full((B, k), -1, jnp.int32),
        )
    if k > N:  # fewer corpus rows than requested: pad with (inf, -1)
        d, i = distance_topk(  # lanns: noqa[LANNS033] -- degenerate k > N tail: k snaps to the corpus size, which callers pre-bucket (quarter-pow2 scan corpora) — one trace per size bucket
            q, x, N, metric, block_q=block_q, block_n=block_n,
            backend=backend, n_valid=nv,
        )
        pad_d = jnp.full((B, k - N), jnp.inf, d.dtype)
        pad_i = jnp.full((B, k - N), -1, i.dtype)
        return jnp.concatenate([d, pad_d], 1), jnp.concatenate([i, pad_i], 1)

    if metric == "cos":
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
        x = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)
        metric_k = "ip"
    else:
        metric_k = metric

    # q/x are already normalized above for 'cos', so the fallbacks must score
    # with metric_k ('ip') — passing 'cos' through would normalize a second
    # time inside ref.distance_matrix (redundant work, not a result change).
    if backend == "jnp":
        return ref.distance_topk_blocked(
            q.astype(jnp.float32), x.astype(jnp.float32), k, metric_k,
            n_valid=nv,
        )

    k_pad = max(next_pow2(k), LANE)
    if k_pad > 256:
        # the in-kernel buffer tops out at 256; larger k streams through the
        # blocked jnp merge instead (rare: paper's k is 100-200).
        return ref.distance_topk_blocked(
            q.astype(jnp.float32), x.astype(jnp.float32), k, metric_k,
            n_valid=nv,
        )
    # pick block_n so the in-kernel merge length k_pad + block_n is a power
    # of two (bitonic network) and a lane multiple.
    block_n = max(block_n, k_pad)
    block_n = next_pow2(k_pad + block_n) - k_pad

    D_pad = round_up(D, LANE)
    B_pad = round_up(B, block_q)
    N_pad = round_up(N, block_n)
    qp = jnp.zeros((B_pad, D_pad), jnp.float32).at[:B, :D].set(q.astype(jnp.float32))  # lanns: noqa[LANNS033] -- D is a deployment constant (one trace per corpus layout); round_up only re-rounds it to the lane width
    xp = jnp.zeros((N_pad, D_pad), jnp.float32).at[:N, :D].set(x.astype(jnp.float32))  # lanns: noqa[LANNS033] -- N arrives pre-bucketed (quarter-pow2 scan corpora); round_up to the kernel block multiple preserves the finite bucket set

    out_d, out_i = distance_topk_pallas(
        qp,
        xp,
        nv,
        k_pad=k_pad,
        block_q=block_q,
        block_n=block_n,
        metric=metric_k,
        interpret=backend == "pallas_interpret",
    )
    out_d, out_i = out_d[:B, :k], out_i[:B, :k]
    if metric == "l2":
        qn = jnp.sum(q.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
        out_d = jnp.where(jnp.isinf(out_d), out_d, out_d + qn)
    out_i = jnp.where(jnp.isinf(out_d), -1, out_i)
    return out_d, out_i


# lanns: hotpath
def distance_topk_q8(
    q,
    qc,
    k: int,
    metric: str = "l2",
    *,
    block_q: int = 8,
    block_n: int = 256,
    backend: str = "auto",
    n_valid: int | None = None,
):
    """Quantized top-k: rank the int8 corpus ``qc`` for each row of ``q``.

    ``qc`` is a ``repro.quant.codec.Q8Corpus`` (or any object with
    ``codes``/``scales``/``norms2``).  Returns (dists, ids) in the same
    convention as :func:`distance_topk`, except distances are the QUANTIZED
    scores — the distance to the dequantized corpus point, with the query
    itself quantized for the integer contraction.  These rank candidates for
    the exact re-rank stage; they are within codec error of the fp32
    distances, not equal to them.

    Backends mirror :func:`distance_topk`: the fused int8 Pallas kernel on
    TPU (or ``pallas_interpret``), and the blocked int8 jnp scan elsewhere —
    both produce bit-identical scores (the dot is exact int32 either way).
    """
    backend = _resolve_backend(backend)
    codes = jnp.asarray(qc.codes)
    scales = np.asarray(qc.scales, np.float32)
    norms2 = jnp.asarray(qc.norms2)
    q = np.asarray(q, np.float32)
    B, D = q.shape
    N = codes.shape[0]
    nv = N if n_valid is None else min(int(n_valid), N)
    if N == 0 or nv == 0:
        return (
            jnp.full((B, k), jnp.inf, jnp.float32),
            jnp.full((B, k), -1, jnp.int32),
        )
    if k > N:
        d, i = distance_topk_q8(  # lanns: noqa[LANNS033] -- degenerate k > N tail: k snaps to the corpus size, which callers pre-bucket (quarter-pow2 q8 corpora) — one trace per size bucket
            q, qc, N, metric, block_q=block_q, block_n=block_n,
            backend=backend, n_valid=nv,
        )
        pad_d = jnp.full((B, k - N), jnp.inf, d.dtype)
        pad_i = jnp.full((B, k - N), -1, i.dtype)
        return jnp.concatenate([d, pad_d], 1), jnp.concatenate([i, pad_i], 1)
    qc_metric = getattr(qc, "metric", None)
    if qc_metric is not None and qc_metric != metric:
        # 'cos' codes are built from normalized rows; scoring them as 'ip'
        # (or vice versa) would silently return wrong rankings.
        raise ValueError(
            f"corpus was quantized for metric={qc_metric!r} but scoring "
            f"requested metric={metric!r}"
        )

    from repro.quant.codec import quantize_queries_q8

    q_eff = q
    if metric == "cos":
        q_eff = q / np.maximum(
            np.linalg.norm(q, axis=-1, keepdims=True), 1e-12
        )
        metric_k = "ip"
    else:
        metric_k = metric
    q_codes, q_scale = quantize_queries_q8(q_eff, scales)

    k_pad = max(next_pow2(k), LANE)
    if backend == "jnp" or k_pad > 256:
        out_d, out_i = ref.distance_topk_q8_blocked(
            jnp.asarray(q_codes), codes, jnp.asarray(q_scale), norms2,
            k, metric_k, n_valid=nv,
        )
    else:
        D_pad = round_up(D, LANE)
        B_pad = round_up(B, block_q)
        block_n = max(block_n, k_pad)
        block_n = next_pow2(k_pad + block_n) - k_pad
        N_pad = round_up(N, block_n)
        qp = np.zeros((B_pad, D_pad), np.int8)
        qp[:B, :D] = q_codes
        xp = jnp.zeros((N_pad, D_pad), jnp.int8).at[:N, :D].set(codes)  # lanns: noqa[LANNS033] -- N arrives pre-bucketed (quarter-pow2 q8 corpora); round_up to the kernel block multiple preserves the finite bucket set
        qsp = np.zeros((B_pad, 1), np.float32)
        qsp[:B, 0] = q_scale
        n2p = jnp.full((1, N_pad), jnp.inf, jnp.float32).at[0, :N].set(norms2)  # lanns: noqa[LANNS033] -- same pre-bucketed N as the codes pad above
        out_d, out_i = distance_topk_q8_pallas(
            jnp.asarray(qp),  # lanns: noqa[LANNS033] -- D is a deployment constant (one trace per corpus layout); round_up only re-rounds it to the lane width
            xp,
            jnp.asarray(qsp),
            n2p,
            nv,
            k_pad=k_pad,
            block_q=block_q,
            block_n=block_n,
            metric=metric_k,
            interpret=backend == "pallas_interpret",
        )
        out_d, out_i = out_d[:B, :k], out_i[:B, :k]
    if metric == "l2":
        qn = jnp.sum(jnp.asarray(q) ** 2, axis=-1, keepdims=True)
        out_d = jnp.where(jnp.isinf(out_d), out_d, out_d + qn)
    out_i = jnp.where(jnp.isinf(out_d), -1, out_i)
    return out_d, out_i


@partial(jax.jit, static_argnames=("k", "metric"))
def distance_topk_jit(q, x, k: int, metric: str = "l2"):
    """Pre-jitted jnp path (stable signature for serving loops)."""
    return ref.distance_topk_blocked(q, x, k, metric)
