"""Composable query-plan executor: route -> candidates -> rerank -> merge.

The paper's serving path is a fixed pipeline (route queries, search each
routed partition, merge); ours composes it from pluggable stages so every
engine x precision x spill combination is a WIRING of shared pieces instead
of a hand-written branch inside ``LannsIndex.query``:

    route       virtual-spill segment routing + compact per-route slot
                layout + perShardTopK — produces a ``QueryPlan``.
    candidates  per-(shard, segment) candidate generation; one stage per
                engine x precision:
                  * fp32 scan   — fused distance+top-k per routed subset
                    (``_Partition.dispatch`` / ``collect``, Pallas kernel
                    on TPU), each scan fetched once the next partition's
                    is dispatched, so its upload overlaps this scan;
                  * q8 scan     — two-stage int8 scan + exact re-rank
                    (``quant.twostage.QuantizedScanExecutor``);
                  * fp32 hnsw   — ONE vmapped ``beam_search_flat`` call over
                    every (partition, routed query) lane of the flat
                    device-resident stack;
                  * q8 hnsw     — the same flat beam over int8 CODES
                    (per-dim scales folded into each lane's query; see
                    ``hnsw._make_row_dist``), then the shared exact re-rank.
    rerank      exact fp32 re-scoring of quantized candidates — the shared
                stage in ``quant/rerank.py``, invoked by both q8 paths.
    merge       THE merge-path decision (``choose_merge_path``) + the
                existing dedup-free ``merge_topk_disjoint_np`` or two-level
                ``merge_topk_vec`` merges, then metric finalization (q8
                ||q||^2 add-back, mips augmented-L2 -> inner-product).

Per-request knobs: a formed micro-batch may carry a DIFFERENT ``(topk, ef)``
per request.  ``knob_groups`` splits the batch into homogeneous groups; the
executor runs each group through the single-knob pipeline (whose inputs pad
to the existing pow2 trace buckets, so no new trace shapes appear) and
reassembles rows in place — bit-identical to issuing each group as its own
homogeneous query (asserted in tests/test_plan.py).

Every stage preserves the pre-refactor numerics exactly: the stage bodies
are the former ``LannsIndex.query`` blocks, moved — not rewritten.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.common.utils import jit_cache_size, next_pow2_quarter
from repro.core.merge import (
    merge_topk_disjoint_np,
    merge_topk_vec,
    per_shard_topk,
)
from repro.obs.telemetry import DETACHED, PlanSpans

# Scale-safety contract for the beam-lane assembly (checked statically by
# repro.analysis.scalecheck at these bounds): up to 4096 partitions of up
# to 2^25 pow2-padded rows each, 2048-d vectors, <=16k routed lanes per
# batch, per-request topk <= 200.
# lanns: dims[n_pad<=33_554_432, pi<=4095, T<=16_384, dim<=2048, pstk<=200]

#: the flat HNSW row lattice (lane offsets, adjacency entries) is int32 on
#: device — every flattened id must stay below this
_INT32_MAX = np.iinfo(np.int32).max

#: routed fp32 scans of a knob group on the device un-fetched, at most: the
#: upload is the serial resource, and while the next partition's corpus
#: streams up, the one before it scans and is fetched; more would only
#: hold more corpora in device memory
_SCANS_IN_FLIGHT = 2


# ---------------------------------------------------------------------------
# Per-request knob normalization / grouping
# ---------------------------------------------------------------------------


def knob_groups(topk, ef, B: int):
    """Normalize (topk, ef) — scalars or per-request arrays — into groups.

    Returns ``(scalar, groups)``:

    * ``scalar`` True: the whole batch shares one knob pair; ``groups`` is
      ``[(topk, ef, None)]`` and the executor runs the no-gather hot path
      (arrays whose entries are all equal collapse here, so a homogeneous
      array costs the same as a scalar).
    * ``scalar`` False: ``groups`` is ``[(topk, ef, rows)]`` sorted by
      ``(topk, ef)`` with ``rows`` ascending — deterministic, and each
      group is exactly a homogeneous sub-query.

    ``ef`` entries <= 0 (or None) mean "index default"; ``topk`` entries
    must be >= 1.
    """
    topk_arr = np.asarray(topk)
    ef_arr = None if ef is None else np.asarray(ef)
    mixed = topk_arr.ndim > 0 or (ef_arr is not None and ef_arr.ndim > 0)
    if not mixed:
        tk = int(topk_arr)
        if tk < 1:
            raise ValueError(f"topk={tk} must be >= 1")
        efv = None if ef is None else int(ef_arr)
        if efv is not None and efv <= 0:
            efv = None  # same contract as array entries: <= 0 == default
        return True, [(tk, efv, None)]
    tks = (
        np.broadcast_to(topk_arr, (B,)).astype(np.int64)
        if topk_arr.ndim == 0
        else topk_arr.astype(np.int64)
    )
    if tks.shape != (B,):
        raise ValueError(
            f"per-request topk has shape {tks.shape} — expected ({B},)"
        )
    if B and tks.min() < 1:
        raise ValueError("per-request topk entries must be >= 1")
    if ef_arr is None:
        efs = np.zeros((B,), np.int64)  # 0 == index default
    else:
        if ef_arr.ndim > 0 and ef_arr.shape != (B,):
            raise ValueError(
                f"per-request ef has shape {ef_arr.shape} — expected ({B},)"
            )
        efs = np.maximum(
            np.broadcast_to(ef_arr, (B,)).astype(np.int64), 0
        )
    groups = []
    for tk, efv in sorted(
        {(int(t), int(e)) for t, e in zip(tks, efs)}
    ):
        rows = np.nonzero((tks == tk) & (efs == efv))[0]
        groups.append((tk, efv if efv > 0 else None, rows))
    if len(groups) == 1:
        tk, efv, _ = groups[0]
        return True, [(tk, efv, None)]
    return False, groups


# ---------------------------------------------------------------------------
# Merge-path decision (the single source; deprecation-window endpoint)
# ---------------------------------------------------------------------------


def choose_merge_path(config, handled=None, partitions=None) -> str:
    """'disjoint' (dedup-free partial sort) vs 'two_level' (lexsort dedup).

    THE one decision point — every call-site (scan fp32/q8, physical spill,
    HNSW, the B == 0 early-out) routes through here instead of re-deriving
    the rule:

    * virtual spill stores each point in exactly ONE (shard, segment), so
      scan-engine candidate ids are disjoint across lanes and the final
      merge needs no dedup -> 'disjoint' (flipped for fp32 scan after its
      deprecation window; parity-tested in tests/test_lanns.py);
    * physical spill duplicates ids across segments -> 'two_level';
    * the HNSW engine (fp32 and q8 beams) keeps 'two_level': its lanes are
      pstk-trimmed, and the two-level merge is the historical contract its
      bit-identity tests pin down;
    * a q8 scan batch only takes 'disjoint' when the two-stage executor
      handled EVERY non-empty partition (its lanes are candidate-wide);
      pass ``handled``/``partitions`` to apply that refinement.
    """
    if config.engine != "scan" or config.spill != "virtual":
        return "two_level"
    if (
        config.quantized == "q8"
        and handled is not None
        and partitions is not None
    ):
        nonempty = {sg for sg, p in partitions.items() if p.size > 0}
        if not handled >= nonempty:
            return "two_level"
    return "disjoint"


def query_stats(pstk, segments_visited, merge_path="two_level",
                knob_groups_count=1):
    """Routing/trace stats dict — one schema for empty and non-empty
    batches (dashboards index these keys unconditionally)."""
    from repro.core import hnsw as hnsw_mod
    from repro.kernels import ref as ref_mod
    from repro.quant import twostage as q8_mod

    empty = segments_visited.size == 0
    return {
        "per_shard_topk": pstk,
        # which final-merge implementation served the batch: 'disjoint'
        # (dedup-free partial sort; scan engine + virtual spill) or
        # 'two_level' (lexsort dedup merge) — 'mixed' when knob groups of
        # one batch took different paths.
        "merge_path": merge_path,
        # how many homogeneous (topk, ef) groups the batch split into
        "knob_groups": knob_groups_count,
        "mean_segments_visited":
            0.0 if empty else float(segments_visited.mean()),
        "max_segments_visited":
            0 if empty else int(segments_visited.max()),
        # process-wide trace counts: serving dashboards watch these to
        # confirm the trace set stays bounded.
        "beam_traces": jit_cache_size(hnsw_mod.beam_search),
        "beam_traces_flat": jit_cache_size(hnsw_mod.beam_search_flat),
        "scan_traces": jit_cache_size(ref_mod.distance_topk_blocked),
        "scan_traces_q8": jit_cache_size(q8_mod._stage1_scores),
    }


# ---------------------------------------------------------------------------
# The plan object + staged executor
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class QueryPlan:
    """Routing result + per-request knobs flowing through the stages."""

    queries: np.ndarray  # (B, d) fp32, metric-prepped (mips-augmented)
    topk: int
    ef: Optional[int]
    hnsw_mode: str
    pstk: int
    lane_width: int  # candidate slots per (query, shard, route) lane
    seg_mask: np.ndarray  # (B, m) routed segments
    slot: np.ndarray  # (B, m) position of segment among the query's routes
    sels: list  # per-segment routed query subsets
    segments_visited: np.ndarray  # (B,)
    max_routes: int
    cand_d: np.ndarray  # (B, S, max_routes, lane_width)
    cand_i: np.ndarray
    handled: set = dataclasses.field(default_factory=set)
    merge_path: str = ""
    # the stages' spans (``obs.PlanSpans`` with telemetry attached): every
    # stage below times its nested spans into it — the q8 re-rank, the fp32
    # scan's uploads and waits.  DETACHED reads no clock at all.
    spans: object = DETACHED


class QueryPlanExecutor:
    """Runs ``QueryPlan``s against one ``LannsIndex``'s partitions.

    Stateless beyond the index reference — the cached device state (HNSW
    stacks, q8 executors) lives on the index, so invalidation stays in one
    place (``LannsIndex._invalidate_stack``).
    """

    def __init__(self, index):
        self.index = index

    # -- stage: route ------------------------------------------------------

    def plan(self, queries, topk, ef, hnsw_mode) -> QueryPlan:
        """Route the batch and lay out the compact candidate slots."""
        index = self.index
        cfg = index.config
        B = queries.shape[0]
        S = cfg.num_shards
        pstk = per_shard_topk(topk, S, cfg.topk_confidence)
        seg_mask = index.partitioner.route_queries(queries)  # (B, m)
        segments_visited = seg_mask.sum(axis=1)
        # slot[b, g]: position of segment g among query b's routed segments.
        slot = np.cumsum(seg_mask, axis=1) - 1
        max_routes = max(int(segments_visited.max()), 1)
        # q8 scan lanes stay candidate-wide (rerank_factor * pstk exactly-
        # scored rows each) so the dedup-free merge sees every candidate;
        # all other engines trim lanes to pstk.
        lane_w = pstk
        if cfg.quantized == "q8" and cfg.engine == "scan" \
                and cfg.spill == "virtual":
            lane_w = min(
                cfg.rerank_factor * pstk,
                max((p.size for p in index.partitions.values()),
                    default=pstk),
            )
            lane_w = max(lane_w, pstk)
        cand_d = np.full((B, S, max_routes, lane_w), np.inf, np.float32)
        cand_i = np.full((B, S, max_routes, lane_w), -1, np.int64)
        # routed query subset per segment — shared by every shard's (s, g)
        # partition, so compute it once.
        sels = [
            np.nonzero(seg_mask[:, g])[0] for g in range(cfg.num_segments)
        ]
        return QueryPlan(
            queries=queries, topk=topk, ef=ef, hnsw_mode=hnsw_mode,
            pstk=pstk, lane_width=lane_w, seg_mask=seg_mask, slot=slot,
            sels=sels, segments_visited=segments_visited,
            max_routes=max_routes, cand_d=cand_d, cand_i=cand_i,
        )

    # -- stage: candidates (engine x precision dispatch) -------------------

    def candidates(self, plan: QueryPlan) -> QueryPlan:
        """Fill the plan's candidate slots; every partition exactly once.

        Routed fp32 scan partitions go in (segment, shard) order, at most
        ``_SCANS_IN_FLIGHT`` un-fetched at once: partition i+1 is
        dispatched (uploaded and queued) before partition i is fetched,
        and each dispatch behind an un-fetched scan is counted by
        ``spans.overlapped()``."""
        index = self.index
        cfg = index.config
        if plan.hnsw_mode == "stacked":
            if cfg.quantized == "q8":
                plan.handled |= self._candidates_hnsw_q8(plan)
            else:
                plan.handled |= self._candidates_hnsw_fp32(plan)
        if cfg.quantized == "q8" and cfg.engine == "scan":
            plan.handled |= index._q8_executor().run(
                plan.queries, plan.sels, plan.slot, plan.cand_d,
                plan.cand_i, plan.pstk, lane_width=plan.lane_width,
                spans=plan.spans,
            )
        n_pad = l_pad = None
        if plan.hnsw_mode == "partition":
            n_pad, l_pad = index._hnsw_pads()
        # fp32 scans dispatched and not yet fetched, oldest first: each is
        # fetched once the next one is dispatched, so one partition's
        # upload streams while the one before it scans
        inflight = collections.deque()
        for g in range(cfg.num_segments):
            sel = plan.sels[g]
            if sel.size == 0:
                continue
            q_sel = plan.queries[sel]
            sl = plan.slot[sel, g]
            for s in range(cfg.num_shards):
                if (s, g) in plan.handled:
                    continue
                part = index.partitions.get((s, g))
                if part is None or part.size == 0:
                    continue
                # the paper propagates the SHARD-level perShardTopK to the
                # segments (never a per-segment trim) — §5.3.2.
                if part.kind == "scan":
                    if inflight:
                        plan.spans.overlapped()
                    inflight.append((part, sel, s, sl, part.dispatch(
                        q_sel, plan.pstk, spans=plan.spans)))
                    if len(inflight) == _SCANS_IN_FLIGHT:
                        self._collect_scan(plan, *inflight.popleft())
                    continue
                d, i = part.search(
                    q_sel, plan.pstk, ef=plan.ef, n_pad=n_pad, l_pad=l_pad,
                    legacy=(plan.hnsw_mode == "legacy"), spans=plan.spans,
                )
                plan.cand_d[sel, s, sl, : plan.pstk] = d
                plan.cand_i[sel, s, sl, : plan.pstk] = i
        while inflight:
            self._collect_scan(plan, *inflight.popleft())
        return plan

    @staticmethod
    def _collect_scan(plan: QueryPlan, part, sel, s, sl, pending) -> None:
        """Fetch one dispatched fp32 scan into its candidate slots."""
        d, i = part.collect(pending, spans=plan.spans)
        plan.cand_d[sel, s, sl, : plan.pstk] = d
        plan.cand_i[sel, s, sl, : plan.pstk] = i

    def _assemble_beam_lanes(self, plan: QueryPlan, stack, q_eff,
                             scales=None):
        """Sparse (partition, routed query) lane buffers for a flat beam.

        The lane layout shared by BOTH beam stages: partition (s, g)
        searches the routed subset of segment g (identical across shards),
        lanes pad to a quarter-pow2 bucket so the call reuses a bounded
        trace set with <= 25% padding waste even under unbalanced segment
        routing.  ``scales`` (P, d), when given, folds each partition's
        per-dim quantization scales into its lanes' queries (the q8 beam's
        dequantized-dot trick).  Returns ``(blocks, handled, Q, OFF, EP,
        V, T)`` — Q/OFF/EP/V are None when no lanes routed (T == 0).
        """
        n_pad = stack["n_pad"]
        blocks = []  # (s, g, pi, lane_start, count)
        q_blocks, off_blocks, ep_blocks = [], [], []
        T = 0
        # sorted(): the stack index is built in (shard, segment) order, but
        # lane layout must not DEPEND on dict insertion order — trace/layout
        # determinism is load-bearing (LANNS006), not incidental.
        for (s, g), pi in sorted(stack["index"].items()):
            sel = plan.sels[g]
            if len(sel) == 0:
                continue
            blocks.append((s, g, pi, T, len(sel)))
            q_blk = q_eff[sel]
            if scales is not None:
                q_blk = q_blk * scales[pi][None, :]
            q_blocks.append(q_blk)
            off = pi * n_pad
            if off + n_pad > _INT32_MAX:
                raise OverflowError(
                    f"beam lane offset {off} + n_pad {n_pad} exceeds the "
                    "int32 flat row lattice — shard the index"
                )
            off_blocks.append(np.full(len(sel), off, np.int32))
            ep_blocks.append(
                np.full(len(sel), stack["entry"][pi] + off, np.int32)
            )
            T += len(sel)
        handled = {(s, g) for (s, g) in stack["index"]}
        if T == 0:
            return blocks, handled, None, None, None, None, 0
        T_pad = next_pow2_quarter(T)
        dim = plan.queries.shape[1]
        Q = np.zeros((T_pad, dim), np.float32)
        OFF = np.zeros((T_pad,), np.int32)
        EP = np.zeros((T_pad,), np.int32)
        Q[:T] = np.concatenate(q_blocks)
        OFF[:T] = np.concatenate(off_blocks)
        EP[:T] = np.concatenate(ep_blocks)
        V = np.arange(T_pad) < T
        return blocks, handled, Q, OFF, EP, V, T

    @staticmethod
    def _cos_normalize(q_eff, hcfg):
        if hcfg.metric != "cos":
            return q_eff
        return q_eff / np.maximum(
            np.linalg.norm(q_eff, axis=-1, keepdims=True), 1e-12
        )

    def _candidates_hnsw_fp32(self, plan: QueryPlan) -> set:
        """One ``beam_search_flat`` call covering every HNSW partition.

        Results scatter into the plan's compact per-route candidate slots;
        returns the set of (shard, segment) partitions served.
        """
        index = self.index
        stack = index._hnsw_stack()
        if not stack:
            return set()
        from repro.core.hnsw import beam_search_flat

        hcfg = index.config.hnsw_config()
        pstk = plan.pstk
        q_eff = self._cos_normalize(plan.queries, hcfg)
        blocks, handled, Q, OFF, EP, V, T = self._assemble_beam_lanes(
            plan, stack, q_eff
        )
        if T == 0:
            return handled
        ef_eff = max(plan.ef or hcfg.ef_search, pstk)
        d_all, i_all = beam_search_flat(  # lanns: noqa[LANNS033] -- pstk ranges over the per-request knob set, finite by the knob_groups contract (not corpus-dependent)
            stack["arrs"],
            jnp.asarray(Q),
            jnp.asarray(EP),
            jnp.asarray(OFF),
            jnp.asarray(V),
            k=pstk,
            ef=ef_eff,
            max_iters=ef_eff + 2 * hcfg.M,
            metric="l2" if hcfg.metric == "l2" else "ip",
        )
        # ONE host sync for all partitions (vs one np.asarray per (s, g))
        d_all, i_all = np.asarray(d_all), np.asarray(i_all)  # lanns: noqa[LANNS003] -- the single designed host sync of the fp32 beam batch
        keys_flat = stack["keys"]
        for (s, g, _pi, start, cnt) in blocks:
            sel = plan.sels[g]
            d = d_all[start: start + cnt]
            i = i_all[start: start + cnt].astype(np.int64)
            i = np.where(i >= 0, keys_flat[np.clip(i, 0, None)], -1)
            sl = plan.slot[sel, g]
            plan.cand_d[sel, s, sl] = d
            plan.cand_i[sel, s, sl] = i
        return handled

    def _candidates_hnsw_q8(self, plan: QueryPlan) -> set:
        """Quantized HNSW beam + shared exact re-rank (AQR-style).

        Candidate generation runs the SAME flat beam as the fp32 stage but
        over the int8-code stack: each lane's query is pre-folded with its
        partition's per-dim scales, so every in-walk distance is a dot
        against the dequantized row at a quarter of the gather bytes.  The
        beam returns ``C = min(rerank_factor * pstk, ef)`` candidates per
        lane ranked by quantized distance; the shared re-rank stage
        (``quant/rerank.py``) re-scores them EXACTLY against the fp32
        originals, and the best ``pstk`` land in the plan slots — so the
        merged results carry no quantization error in their distances, only
        (bounded) candidate-selection error, exactly like the q8 scan.
        """
        index = self.index
        stack = index._hnsw_stack(quantized=True)
        if not stack:
            return set()
        from repro.core.hnsw import beam_search_flat
        from repro.quant.rerank import exact_candidate_distances

        cfg = index.config
        hcfg = cfg.hnsw_config()
        pstk = plan.pstk
        # beam walk + rerank both use the hnsw-internal metric ('cos' rows
        # were normalized at build, so their exact scores reduce to 'ip' —
        # matching the fp32 beam's returned distances)
        rmetric = "l2" if hcfg.metric == "l2" else "ip"
        q_eff = self._cos_normalize(plan.queries, hcfg)
        n_pad = stack["n_pad"]
        ef_eff = max(plan.ef or hcfg.ef_search, pstk)
        # candidate width: rerank up to rerank_factor * pstk of the beam's
        # ef entries — the beam's exploration budget stays the user's ef
        C = max(min(cfg.rerank_factor * pstk, ef_eff), pstk)
        blocks, handled, Q, OFF, EP, V, T = self._assemble_beam_lanes(
            plan, stack, q_eff, scales=stack["scales"]
        )
        if T == 0:
            return handled
        d_all, i_all = beam_search_flat(
            stack["arrs"],  # int8 codes + norms2: quantized walk
            jnp.asarray(Q),
            jnp.asarray(EP),
            jnp.asarray(OFF),
            jnp.asarray(V),
            k=C,
            ef=ef_eff,
            max_iters=ef_eff + 2 * hcfg.M,
            metric=rmetric,
        )
        i_all = np.asarray(i_all)  # lanns: noqa[LANNS003] -- the single designed host sync of the q8 beam batch (quantized d_all is discarded: re-ranked)
        stores = stack["stores"]
        store_mode = stack["store_mode"]
        for (s, g, pi, start, cnt) in blocks:
            sel = plan.sels[g]
            store = stores[pi]
            rows = i_all[start: start + cnt]  # (b, C) flat rows, -1 padded
            invalid = rows < 0
            # int64 intermediate: `rows - pi * n_pad` in the rows' own int32
            # would wrap for partitions past the 2^31 boundary; the clip
            # result is < store.size, so the narrowing cast back is exact
            cand = np.clip(
                rows.astype(np.int64) - pi * n_pad, 0, store.size - 1
            ).astype(np.int32)
            with plan.spans.span("rerank"):
                ex = exact_candidate_distances(
                    q_eff[sel], cand, store, rmetric,
                    mode=store_mode, l_pad=next_pow2_quarter(cnt),
                )
            ex = np.where(invalid, np.inf, ex)
            kk = min(pstk, C)
            if kk < C:
                loc = np.argpartition(ex, kk - 1, axis=1)[:, :kk]
                d_lane = np.take_along_axis(ex, loc, axis=1)
                cand_sel = np.take_along_axis(cand, loc, axis=1)
            else:
                d_lane = ex
                cand_sel = cand
            i_lane = np.where(
                np.isinf(d_lane), -1, store.keys[cand_sel]
            )
            sl = plan.slot[sel, g]
            plan.cand_d[sel, s, sl, :kk] = d_lane
            plan.cand_i[sel, s, sl, :kk] = i_lane
        return handled

    # -- stage: merge + metric finalization --------------------------------

    def merge(self, plan: QueryPlan):
        """Two-level (or dedup-free) merge + metric corrections."""
        index = self.index
        cfg = index.config
        B = plan.queries.shape[0]
        S = cfg.num_shards
        plan.merge_path = choose_merge_path(
            cfg, plan.handled, index.partitions
        )
        if plan.merge_path == "disjoint":
            # dedup-free merge over every candidate (a superset of what
            # perShardTopK trimming would forward, so recall can only
            # improve); physical spill (duplicate ids) takes the
            # merge_topk_vec branch below instead.
            out_d, out_i = merge_topk_disjoint_np(
                plan.cand_d.reshape(B, S * plan.max_routes * plan.lane_width),
                plan.cand_i.reshape(B, S * plan.max_routes * plan.lane_width),
                plan.topk,
            )
        else:
            # level-1: segment merge inside each shard, all (query, shard)
            # rows in one vectorized call.
            shard_d, shard_i = merge_topk_vec(
                plan.cand_d.reshape(B * S, plan.max_routes * plan.lane_width),
                plan.cand_i.reshape(B * S, plan.max_routes * plan.lane_width),
                plan.pstk,
            )
            # level-2: broker merge over shards.
            out_d, out_i = merge_topk_vec(
                shard_d.reshape(B, S * plan.pstk),
                shard_i.reshape(B, S * plan.pstk),
                plan.topk,
            )
        if cfg.quantized == "q8" and cfg.metric in ("l2", "mips"):
            # q8 lane distances omit the per-query ||q||^2 constant (it
            # cannot change any within-query ordering); restore true
            # squared distances with one (B, topk) add.
            qn8 = np.einsum("bd,bd->b", plan.queries, plan.queries)
            out_d = np.where(
                np.isfinite(out_d), out_d + qn8[:, None], out_d
            )
        if cfg.metric == "mips":
            # convert augmented-L2 distances back to (negated) inner
            # products: d^2 = M^2 + |q|^2 - 2<q, x>
            #   =>  -<q, x> = (d^2 - M^2 - |q|^2) / 2
            q_raw = plan.queries[:, :-1]
            qn = np.einsum("bd,bd->b", q_raw, q_raw)
            out_d = np.where(
                np.isfinite(out_d),
                (out_d - index._mips_M2 - qn[:, None]) / 2.0,
                np.inf,
            )
        return out_d, out_i

    # -- one homogeneous (single-knob) pass --------------------------------

    # lanns: hotpath
    def execute(self, queries, topk, ef, hnsw_mode):
        """route -> candidates (-> rerank) -> merge for ONE knob group.

        With ``index.telemetry`` attached (an ``obs.Telemetry``), each stage
        runs in a span of ``PlanSpans(telemetry)`` — a duration on the
        telemetry clock and a ``lanns.<stage>`` profiler annotation — and
        the group is reported through ``telemetry.on_execute`` (labeled by
        engine/quantized/merge_path/pow2 batch bucket).  Detached (the
        default), the stages run in ``DETACHED`` spans — no clock reads, no
        annotations, no telemetry calls — so instrumentation-off results
        are structurally bit-identical to -on (asserted in
        tests/test_obs.py).
        """
        tel = getattr(self.index, "telemetry", None)
        spans = DETACHED if tel is None else PlanSpans(tel)
        with spans.span("route"):
            plan = self.plan(queries, topk, ef, hnsw_mode)
        plan.spans = spans
        with spans.span("candidates"):
            self.candidates(plan)
        with spans.span("merge"):
            out_d, out_i = self.merge(plan)
        if tel is not None:
            cfg = self.index.config
            tel.on_execute(
                engine=cfg.engine, quantized=cfg.quantized,
                merge_path=plan.merge_path, batch=queries.shape[0],
                spans=spans,
            )
        return out_d, out_i, plan
