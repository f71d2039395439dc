"""Two-stage (int8 scan -> exact re-rank) executor for the scan engine.

Stage 1 scores each routed (shard, segment)'s int8 corpus and keeps
``rerank_factor * perShardTopK`` candidates per (query, partition) lane;
stage 2 computes EXACT fp32 distances for just those candidates and the
executor merges the exact results.  Full-precision recall at a fraction of
the scan bytes: the resident scan corpus is int8 codes (+ 8 bytes/vector of
corrections), and the fp32 originals only serve candidate lookups.

Backend strategy (what actually runs where):

* stage-1 scoring is one jitted call per partition, dispatched async for
  every partition FIRST so XLA's pool computes later partitions while the
  host selects/re-ranks earlier ones.  On CPU the int8 dot is computed by
  casting codes to fp32 INSIDE the jit and running the oneDNN gemm —
  bit-exact to the int32 dot for D <= 1024 (products sum below 2^24) and
  measurably faster than the fp32 scan's gemm because the operand traffic
  halves.  On TPU / for D > 1024 it is a true int8->int32 ``dot_general``
  (the fused Pallas kernel in ``kernels/distance_topk_q8.py`` is the
  device-side equivalent that also fuses the top-k).
* candidate selection runs host-side via ``np.argpartition`` (O(N)
  introselect — measured ~3x cheaper than ``lax.top_k`` on CPU for the
  bench shapes) on a zero-copy dlpack view of the device scores.
* the exact re-rank is the SHARED stage in ``quant/rerank.py`` (also used
  by the q8 HNSW beam): density-adaptive host scoring (dense gemm when the
  candidate volume rivals the segment, row gathers otherwise) or a jitted
  device gather, selected by ``rerank_store``.

Shapes are bucketed exactly like the rest of the serving stack: corpora pad
to shared pow2 size buckets, lane counts to quarter-pow2 buckets, so the
jitted stage-1/stage-2 calls reuse a bounded trace set.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.utils import next_pow2_quarter
from repro.obs.telemetry import DETACHED
from repro.quant.codec import EPS_SCALE, Q8Corpus
from repro.quant.rerank import (
    ExactStore,
    exact_candidate_distances,
    resolve_store_mode,
)

# stage-1 fp32-cast gemm is exact (= the int32 dot) while every int8 product
# sum stays below 2^24: D * 127^2 <= 2^24  =>  D <= 1040.
_EXACT_CAST_MAX_D = 1024


@partial(jax.jit, static_argnames=("mult", "exact_cast"))
def _stage1_scores(q, codes, scale_bias, mult, exact_cast):
    """(L, Npad) quantized scores, lower is better.

    ``q`` is fp32 (pre-normalized by the caller for 'cos'); query
    quantization (scale folding + per-query symmetric int8) happens inside
    the jit.  ``scale_bias`` is (D + Npad,): the per-dim scales followed by
    a per-row bias that folds BOTH the metric correction and the padding
    mask — dequantized ||x||^2 with +inf padding for l2 (mult=-2), plain
    0/+inf for ip (mult=-1) — so no iota/where runs per call.
    """
    dim = q.shape[1]
    scales = scale_bias[:dim]
    bias = scale_bias[dim:]
    qf = q * scales[None, :]
    qsc = jnp.maximum(jnp.abs(qf).max(-1) / 127.0, EPS_SCALE)
    qcf = jnp.rint(qf / qsc[:, None])  # integer-valued fp32 in [-127, 127]
    if exact_cast:
        dots = jax.lax.dot_general(
            qcf, codes.astype(jnp.float32), (((1,), (1,)), ((), ()))
        )
    else:
        dots = jax.lax.dot_general(
            qcf.astype(jnp.int8), codes, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        ).astype(jnp.float32)
    return bias[None, :] + (mult * qsc)[:, None] * dots


class _Q8Partition:
    """Device/host state for one quantized (shard, segment) partition."""

    # Deployment envelope (repro.analysis.scalecheck): one segment of the
    # paper's q8 deployment point — 10M rows (12.5M after the quarter-pow2
    # pad) x 512d codes must fit a single 8 GiB device alongside headroom.
    # lanns: dims[n_pad<=12_500_000, dim<=512]
    def __init__(self, qc: Q8Corpus, vectors: np.ndarray, keys, metric: str):  # lanns: budget[device<=8GiB]
        self.n = qc.size
        # quarter-pow2 corpus buckets: stage-1 gemm cost and resident codes
        # scale with n_pad, so cap padding waste at 25% (vs up to 2x for
        # plain pow2) while the trace count stays logarithmic.
        self.n_pad = next_pow2_quarter(self.n)
        dim = qc.dim
        codes = np.zeros((self.n_pad, dim), np.int8)
        codes[: self.n] = qc.codes
        # scales ++ per-row bias folding the metric correction AND the
        # padding mask (l2 uses the dequantized norms, ip a zero bias; +inf
        # on padding rows) — only the served metric's vector stays resident.
        metric_k = "l2" if metric == "l2" else "ip"
        bias = np.full((self.n_pad,), np.inf, np.float32)
        bias[: self.n] = qc.norms2 if metric_k == "l2" else 0.0
        self.codes = jnp.asarray(codes)
        self.scale_bias = {
            metric_k: jnp.asarray(np.concatenate([qc.scales, bias])),
        }
        # exact store: fp32 originals stay host-side (numpy / mmap) unless
        # rerank_store='device' uploads them lazily.
        self.store = ExactStore(vectors, keys)
        self.metric = metric

    @property
    def keys(self):
        return self.store.keys

    def resident_bytes(self) -> int:
        """Scan-resident footprint: codes + scale/bias vectors."""
        return int(self.codes.nbytes) + sum(
            int(v.nbytes) for v in self.scale_bias.values()
        )


class QuantizedScanExecutor:
    """Runs the two-stage search for every quantized scan partition.

    Built once per index (device codes upload once, like the HNSW stack) and
    reused across query batches; ``run`` scatters per-lane exact results
    into the executor's compact route slots, mirroring the stacked-HNSW
    candidates stage in ``core/plan.py``.
    """

    def __init__(self, parts, metric: str, rerank_factor: int,
                 rerank_store: str):
        # parts: {(s, g): _Q8Partition}
        self.parts = parts
        self.metric = metric
        self.rerank_factor = max(int(rerank_factor), 1)
        self.rerank_store = resolve_store_mode(rerank_store)

    def resident_bytes(self) -> int:
        return sum(p.resident_bytes() for p in self.parts.values())

    def exact_store_bytes(self) -> int:
        return sum(p.store.nbytes() for p in self.parts.values())

    # -- the full two-stage pass ------------------------------------------

    # lanns: hotpath
    def run(self, queries, sels, slot, cand_d, cand_i, pstk, *,
            lane_width=None, spans=DETACHED):
        """Search every quantized partition; returns the handled set.

        ``queries`` are the raw fp32 queries (mips augmentation already
        applied by the caller; metric == 'l2' then).  Lane results land in
        ``cand_d``/``cand_i`` route slots of width ``lane_width``
        (default ``pstk``): the dedup-free merge path passes the full
        candidate width ``rerank_factor * pstk`` so lanes skip the
        per-lane trim and the merge sees every exactly-scored candidate.

        For metric 'l2' the scattered distances OMIT the per-query ||q||^2
        constant (it cannot change any within-query ordering); the caller
        adds it back after its merge — one (B, topk) add instead of one per
        lane.

        ``spans`` (an ``obs.PlanSpans``): every partition's exact re-rank
        runs in its ``rerank`` span.  Left at ``DETACHED`` (the default) no
        clock is read: the untimed path is byte-for-byte the pre-telemetry
        one.
        """
        handled = set(self.parts)
        W = pstk if lane_width is None else lane_width
        q_eff = np.asarray(queries, np.float32)
        if self.metric == "cos":
            q_eff = q_eff / np.maximum(
                np.linalg.norm(q_eff, axis=-1, keepdims=True), 1e-12
            )
        metric_k = "l2" if self.metric == "l2" else "ip"
        mult = -2.0 if metric_k == "l2" else -1.0
        # phase A: async-dispatch every partition's stage-1 scores; XLA's
        # pool computes later partitions while the host handles earlier ones
        staged = []
        # sorted(): dispatch order must not depend on dict insertion order —
        # it fixes both the XLA dispatch sequence and the scatter order
        # (LANNS006); parts is built sorted, so this is bit-identical.
        for (s, g), part in sorted(self.parts.items()):
            sel = sels[g]
            b = len(sel)
            if b == 0 or part.n == 0:
                continue
            l_pad = next_pow2_quarter(b)
            q_lane = q_eff[sel]
            qp = q_lane
            if l_pad != b:
                qp = np.zeros((l_pad, q_eff.shape[1]), np.float32)
                qp[:b] = q_lane
            fut = _stage1_scores(
                jnp.asarray(qp), part.codes, part.scale_bias[metric_k],  # lanns: noqa[LANNS004] -- per-partition ASYNC dispatch is the point: uploads overlap stage-1 compute
                mult, part.codes.shape[1] <= _EXACT_CAST_MAX_D,
            )
            staged.append(((s, g), part, sel, b, l_pad, q_lane, fut))
        # phase B: select -> exact re-rank -> scatter, one partition at a time
        host_shares_memory = jax.default_backend() == "cpu"
        for (s, g), part, sel, b, l_pad, q_lane, fut in staged:
            C = min(self.rerank_factor * pstk, part.n)
            # CPU jax shares buffers with numpy via dlpack (zero-copy view;
            # selection only reads it); accelerators need the device->host
            # copy — np.from_dlpack refuses non-CPU capsules.
            scores = (
                np.from_dlpack(fut) if host_shares_memory  # lanns: noqa[LANNS003] -- per-partition sync AFTER async dispatch of all partitions; zero-copy on CPU
                else np.asarray(fut)  # lanns: noqa[LANNS003] -- accelerator fallback of the same designed sync point
            )[:b]
            if C < scores.shape[1]:
                # padding rows score +inf, so the C smallest are always
                # real rows (C <= n == number of finite entries)
                cand = np.argpartition(scores, C, axis=1)[:, :C].astype(
                    np.int32
                )
            else:  # C == n == n_pad: every row is a candidate
                cand = np.broadcast_to(
                    np.arange(C, dtype=np.int32), (b, C)
                ).copy()
            with spans.span("rerank"):
                ex = exact_candidate_distances(
                    q_lane, cand, part.store, self.metric,
                    mode=self.rerank_store, l_pad=l_pad,
                )
            kk = min(W, C)
            if kk < C:
                loc = np.argpartition(ex, kk - 1, axis=1)[:, :kk]
                d_lane = np.take_along_axis(ex, loc, axis=1)
                i_lane = part.keys[np.take_along_axis(cand, loc, axis=1)]
            else:
                d_lane = ex
                i_lane = part.keys[cand]
            sl = slot[sel, g]
            cand_d[sel, s, sl, :kk] = d_lane
            cand_i[sel, s, sl, :kk] = i_lane
        return handled
