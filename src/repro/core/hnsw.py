"""Hierarchical Navigable Small World (HNSW) index — array form.

LANNS (§3) uses HNSW [Malkov & Yashunin 2016] as the per-partition ANN engine.
This module implements HNSW faithfully in two halves that mirror the paper's
offline/online split:

* **Build** (offline — the paper builds inside Spark executors): a numpy
  implementation of Algorithms 1–4 of the HNSW paper (insert with greedy
  descent, ef_construction beam at each level, and the neighbor-selection
  heuristic).  The graph lives in preallocated flat int32 adjacency arrays
  with degree counters (amortized-doubling growth across ``add_batch``
  calls), and insertion runs in deterministic *wavefront chunks*: level
  draws are batched per call, and the phase-1 greedy descent of every
  level-0 point in a chunk runs as ONE vectorized batched walk against the
  frozen spine graph (only points with level >= 1 ever mutate the upper
  levels, so the descent of a level-0 run is a pure function of spine
  state — the order-dependent level-0 connect/prune phase stays sequential
  within the chunk, which makes the built graph bit-identical for a fixed
  seed regardless of chunk size or worker count).  Per-index build is still
  sequential where HNSW requires it; LANNS gets its build parallelism
  *across* partitions (one HNSW per (shard, segment)), which is exactly
  what ``repro.core.lanns`` does.  ``HNSWIndexLegacy`` keeps the
  pre-wavefront python-list/heapq builder as the before/after benchmark
  baseline and recall oracle.

* **Search** (online — the serving hot path): the frozen index is a set of
  fixed-shape int32 adjacency arrays, and search is a jit/vmap-compatible
  beam search written with ``jax.lax`` control flow so it runs under
  ``shard_map`` on a TPU mesh.  This is the TPU adaptation described in
  DESIGN.md §2: instead of pointer-chasing over a heap-allocated graph, we
  keep a top-``ef`` beam as dense (ids, dists, expanded) arrays and expand the
  best unexpanded node each iteration with a batched gather + MXU-friendly
  distance block.

Frozen layout
-------------
``vectors``      (n, d)  float32   — corpus (cosine-normalized if metric=cos)
``adj0``         (n, 2M) int32     — level-0 adjacency, -1 padded
``upper_adj``    (L, n, M) int32   — adjacency at levels 1..L, indexed by
                                     GLOBAL id (-1 rows for nodes absent at
                                     that level), so one fixed-shape stack
                                     replaces the ragged per-level lists
``entry``        int               — entry point (top-level node)

Trace stability (the serving contract): ``device_arrays`` pads ``n`` and
``L`` to caller-chosen buckets and caches the resulting device pytree on the
index, so (a) the graph uploads host->device ONCE per (n_pad, l_pad) bucket,
and (b) every partition padded to the same bucket reuses one ``beam_search``
trace.  ``beam_search_flat`` goes further and runs ALL partitions of an
index in a single vmapped call over flattened (partition, query) lanes —
the ``LannsIndex.query`` hot path; ``beam_search_stacked`` is the dense
(P, C) variant kept for the TPU dispatch comparison (ROADMAP).
"""

from __future__ import annotations

import dataclasses
import heapq
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

_INF = np.float32(np.inf)


@dataclasses.dataclass(frozen=True)
class HNSWConfig:
    """Build/search parameters (HNSW paper notation).

    M:                max out-degree at levels >= 1 (level 0 uses 2M).
    ef_construction:  beam width during insertion.
    ef_search:        default beam width during search (>= k).
    metric:           'l2' (squared euclidean), 'ip' (inner product, maximize),
                      'cos' (cosine; vectors are L2-normalized at build/query).
    extend_candidates / keep_pruned: Algorithm 4 switches.
    """

    M: int = 16
    ef_construction: int = 100
    ef_search: int = 100
    metric: str = "l2"
    seed: int = 0
    extend_candidates: bool = False
    keep_pruned: bool = True
    max_level_cap: int = 12

    @property
    def m_l(self) -> float:
        return 1.0 / math.log(self.M)

    @property
    def m_max0(self) -> int:
        return 2 * self.M


def _normalize_rows(x: np.ndarray) -> np.ndarray:
    n = np.linalg.norm(x, axis=-1, keepdims=True)
    return x / np.maximum(n, 1e-12)


def pairwise_dist(metric: str, q: np.ndarray, x: np.ndarray) -> np.ndarray:
    """Distance from one query vector to rows of x.  Lower is better."""
    if metric == "l2":
        diff = x - q
        return np.einsum("nd,nd->n", diff, diff)
    # ip / cos: score = -<q, x> so "lower is better" stays uniform.
    return -(x @ q)


#: default wavefront chunk: the max number of consecutive level-0 points
#: whose phase-1 descent is batched into one vectorized walk.  Any value
#: yields the same graph (descent of a level-0 run is a pure function of the
#: frozen spine); 256 amortizes the numpy dispatch overhead without making
#: the (chunk, M, d) gather buffers large.
DEFAULT_BUILD_CHUNK = 256

#: best-first expansion batch: per beam round, up to this many candidate
#: nodes are popped together and their neighborhoods scored in one
#: vectorized block.  Deterministic (pops follow the same (dist, id) heap
#: order) and per-query local, so it never affects chunk/worker invariance;
#: it trades a few extra distance evaluations for ~B fewer numpy dispatches
#: per round, which dominates single-core build time.
_EXPAND_BATCH = 16

_MIN_CAP = 1024
_MIN_UPPER_CAP = 64


class HNSWIndex:
    """A single HNSW graph over one data partition (bulk wavefront builder).

    Storage is flat preallocated arrays with amortized-doubling growth, so
    repeated ``add_batch`` calls (the streaming-mutability precursor) are
    linear instead of re-concatenating the corpus per call:

    ``_vstack``  (cap, d) float32  corpus rows (cos rows pre-normalized)
    ``_adj0``    (cap, 2M) int32   level-0 adjacency, -1 beyond ``_deg0``
    ``_uadj[l]`` (cap_l, M) int32  level-(l+1) adjacency rows (slot-compact:
                                   only the ~n/M^(l+1) nodes present at that
                                   level own a row; ``_uslot[l]`` maps global
                                   id -> row, -1 when absent)

    Determinism contract: for a fixed config seed and insertion order, the
    built graph is bit-identical regardless of the wavefront ``chunk`` size
    and of how many process-pool workers build sibling partitions — and an
    ``add_batch(a); add_batch(b)`` sequence equals ``add_batch(a + b)``
    (level draws consume the generator stream element-wise).
    """

    def __init__(self, config: HNSWConfig, dim: int):
        self.config = config
        self.dim = dim
        self._n = 0
        self._cap = 0
        # adjacency rows carry slack beyond m_max (Vamana-style deferred
        # pruning): appends are plain writes until the row physically fills,
        # then one heuristic prune compacts it back to m_max.  freeze()
        # prunes any row still above m_max down to the frozen width.
        self._w0 = config.m_max0 + config.M
        self._wu = config.M + max(config.M // 2, 1)
        self._vstack = np.zeros((0, dim), dtype=np.float32)
        self._norms = np.zeros((0,), dtype=np.float32)
        self._levels = np.zeros((0,), dtype=np.int32)
        self._adj0 = np.zeros((0, self._w0), dtype=np.int32)
        self._deg0 = np.zeros((0,), dtype=np.int32)
        # upper levels (index ul = level - 1), slot-compact
        self._uslot: list[np.ndarray] = []  # (cap,) int32 global id -> row
        self._uadj: list[np.ndarray] = []   # (cap_l, M) int32 global ids
        self._udeg: list[np.ndarray] = []   # (cap_l,) int32
        self._ucount: list[int] = []        # rows in use per upper level
        self.entry: int = -1
        self.max_level: int = -1
        self._rng = np.random.default_rng(config.seed)
        self._frozen = None
        self._visited = np.zeros(0, dtype=np.int64)
        self._visit_gen = 0
        self.keys: Optional[np.ndarray] = None  # original (global) keys

    # ------------------------------------------------------------------
    # Storage growth (amortized doubling)
    # ------------------------------------------------------------------

    @property
    def size(self) -> int:
        return self._n

    def _ensure_capacity(self, n_total: int) -> None:
        if n_total <= self._cap:
            return
        cap = max(self._cap * 2, n_total, _MIN_CAP)
        n = self._n

        def grown(old, shape_tail, fill, dtype):
            new = np.full((cap, *shape_tail), fill, dtype=dtype)
            new[:n] = old[:n]
            return new

        self._vstack = grown(self._vstack, (self.dim,), 0.0, np.float32)
        self._norms = grown(self._norms, (), 0.0, np.float32)
        self._levels = grown(self._levels, (), 0, np.int32)
        self._adj0 = grown(self._adj0, (self._w0,), -1, np.int32)
        self._deg0 = grown(self._deg0, (), 0, np.int32)
        # visited stamps survive growth: new rows are 0 = never visited, and
        # the generation counter is never reset.  One sentinel slot rides at
        # index `cap`: -1 adjacency padding wraps onto it under
        # ``take(mode="wrap")`` and it is pre-stamped per search, so padding
        # is dropped by the same filter as visited nodes.
        visited = np.zeros(cap + 1, dtype=np.int64)
        visited[:n] = self._visited[:n]
        self._visited = visited
        self._uslot = [grown(s, (), -1, np.int32) for s in self._uslot]
        self._cap = cap

    def _register_upper(self, i: int, lvl: int) -> None:
        """Give node ``i`` an adjacency row at every level 1..lvl (creating
        levels that did not exist yet).  Slot order == insertion order."""
        wu = self._wu
        while len(self._uadj) < lvl:
            self._uslot.append(np.full(self._cap, -1, dtype=np.int32))
            self._uadj.append(
                np.full((_MIN_UPPER_CAP, wu), -1, dtype=np.int32)
            )
            self._udeg.append(np.zeros(_MIN_UPPER_CAP, dtype=np.int32))
            self._ucount.append(0)
        for ul in range(lvl):
            row = self._ucount[ul]
            if row == self._uadj[ul].shape[0]:
                cap_l = row * 2
                new_adj = np.full((cap_l, wu), -1, dtype=np.int32)
                new_adj[:row] = self._uadj[ul]
                self._uadj[ul] = new_adj
                new_deg = np.zeros(cap_l, dtype=np.int32)
                new_deg[:row] = self._udeg[ul]
                self._udeg[ul] = new_deg
            self._uslot[ul][i] = row
            self._ucount[ul] = row + 1

    # ------------------------------------------------------------------
    # Distance / adjacency primitives (build hot path)
    # ------------------------------------------------------------------

    def _dist(self, q: np.ndarray, ids: np.ndarray, q2: float) -> np.ndarray:
        """Distances from ``q`` (with precomputed ``q2 = <q, q>``) to rows
        ``ids``.  Lower is better; 'l2' returns true squared distances."""
        vecs = self._vstack[ids]
        if self.config.metric == "l2":
            return self._norms[ids] - 2.0 * (vecs @ q) + q2
        return -(vecs @ q)

    def _q2(self, q: np.ndarray) -> float:
        return float(q @ q) if self.config.metric == "l2" else 0.0

    # ------------------------------------------------------------------
    # Phase 1: vectorized wavefront greedy descent (spine levels)
    # ------------------------------------------------------------------

    def _descend(self, Q: np.ndarray, stops: np.ndarray, upper=None):
        """Greedy descent for a whole chunk in one batched walk.

        Lane ``c`` of ``Q`` walks levels ``max_level .. stops[c]+1``, moving
        to its best-improving neighbor until a local minimum, exactly like
        the serving path's upper-level loop (``_beam_search_lanes``).  Only
        nodes with level >= 1 ("spine" nodes) own upper-level adjacency and
        only spine insertions mutate it, so for a run of level-0 points this
        is a pure function of the frozen spine graph — the batched result is
        bit-identical to descending each point alone, whatever the chunk
        size.  Scores are rank-equivalent surrogates (l2 drops the constant
        ``<q, q>`` term); callers re-score entry points exactly.

        Returns ``(ep, ep_d)``: per-lane entry node and surrogate score.
        """
        C = Q.shape[0]
        ep = np.full(C, self.entry, dtype=np.int64)
        ve = self._vstack[self.entry]
        if self.config.metric == "l2":
            ep_d = self._norms[self.entry] - 2.0 * (Q @ ve)
        else:
            ep_d = -(Q @ ve)
        for level in range(self.max_level, 0, -1):
            act = np.flatnonzero(stops < level)
            if act.size == 0:
                continue
            ul = level - 1
            if upper is None:
                slot, adj = self._uslot[ul], self._uadj[ul]
            else:  # frozen upper adjacency: global-id indexed, no slots
                slot, adj = None, upper[ul]
            while act.size:
                rows = ep[act] if slot is None else slot[ep[act]]
                nbrs = adj[rows]  # (a, M) global ids, -1 padded
                safe = np.clip(nbrs, 0, None)
                dots = np.matmul(
                    self._vstack[safe], Q[act][:, :, None]
                )[:, :, 0]
                if self.config.metric == "l2":
                    dn = self._norms[safe] - 2.0 * dots
                else:
                    dn = -dots
                dn[nbrs < 0] = np.inf
                j = np.argmin(dn, axis=1)
                ar = np.arange(act.size)
                bd = dn[ar, j]
                better = bd < ep_d[act]
                if not better.any():
                    break
                moved = act[better]
                ep[moved] = nbrs[ar[better], j[better]]
                ep_d[moved] = bd[better]
                act = moved
        return ep, ep_d

    # ------------------------------------------------------------------
    # Algorithm 2 — beam search at one level (sequential, vectorized inner)
    # ------------------------------------------------------------------

    def _search_layer(self, q, entry_points, ef, level, adj0=None):
        """Best-first beam of width ``ef``.  Returns (dists, ids) ascending.

        Same W-set semantics as the classic heapq formulation, with two
        single-core throughput changes: per round, up to ``_EXPAND_BATCH``
        heap candidates are popped together (same (dist, id) pop order) and
        their joint neighborhood is visited-filtered + scored in ONE
        vectorized block, and once the beam is full only neighbors beating
        the current worst are pushed.
        """
        visited = self._visited
        self._visit_gen += 1
        gen = self._visit_gen
        q2 = self._q2(q)
        vstack = self._vstack
        norms = self._norms
        l2 = self.config.metric == "l2"
        heappush, heappop = heapq.heappush, heapq.heappop
        heapreplace = heapq.heapreplace
        if level == 0:
            adj, slot = (self._adj0 if adj0 is None else adj0), None
        else:
            ul = level - 1
            adj, slot = self._uadj[ul], self._uslot[ul]

        eps = np.asarray(entry_points, dtype=np.int64)
        if eps.size > 1:
            eps = np.unique(eps)
        if l2:
            d0 = norms[eps] - 2.0 * (vstack[eps] @ q) + q2
        else:
            d0 = -(vstack[eps] @ q)
        visited[eps] = gen
        visited[self._cap] = gen  # sentinel: -1 padding wraps onto it
        cand = list(zip(d0.tolist(), eps.tolist()))  # min-heap by dist
        heapq.heapify(cand)
        best = [(-d, e) for d, e in cand]  # max-heap by -dist (the W set)
        heapq.heapify(best)
        while len(best) > ef:
            heappop(best)
        full = len(best) >= ef
        d_worst = -best[0][0]
        batch = np.empty(_EXPAND_BATCH, dtype=np.int64)

        while cand:
            nb = 0
            while cand and nb < _EXPAND_BATCH:
                d_c = cand[0][0]
                if d_c > d_worst and full:
                    break
                batch[nb] = heappop(cand)[1]
                nb += 1
            if nb == 0:
                break
            rows = batch[:nb]
            nbrs = (adj[rows] if slot is None else adj[slot[rows]]).ravel()
            # -1 padding wraps to the pre-stamped sentinel slot, so one
            # filter drops both padding and already-visited nodes
            nbrs = nbrs[visited.take(nbrs, mode="wrap") != gen]
            if nbrs.size == 0:
                continue
            if nb > 1:  # batch rows can share neighbors: sorted dedup
                nbrs.sort()
                if nbrs[0] != nbrs[-1]:
                    keep = np.empty(nbrs.size, dtype=bool)
                    keep[0] = True
                    np.not_equal(nbrs[1:], nbrs[:-1], out=keep[1:])
                    nbrs = nbrs[keep]
                else:
                    nbrs = nbrs[:1]
            visited[nbrs] = gen
            vecs = np.take(vstack, nbrs, axis=0)
            if l2:
                dn = vecs @ q
                dn *= -2.0
                dn += np.take(norms, nbrs)
                dn += q2
            else:
                dn = vecs @ q
                dn *= -1.0
            if full:
                # only candidates beating the current worst can enter the
                # beam; the exact per-item check below still runs.
                keep = dn < d_worst
                nbrs = nbrs[keep]
                dn = dn[keep]
                if nbrs.size == 0:
                    continue
            if dn.size > 8:
                # process ascending: d_worst tightens fastest, and once one
                # neighbor misses the beam every later one must too — the
                # loop breaks instead of heap-churning through the tail.
                # (stable sort: ids are ascending after dedup, so ties are
                # deterministic.)
                o = np.argsort(dn, kind="stable")
                dn = dn[o]
                nbrs = nbrs[o]
                srt = True
            else:
                srt = False
            for d, u in zip(dn.tolist(), nbrs.tolist()):
                if not full:
                    heappush(cand, (d, u))
                    heappush(best, (-d, u))
                    if len(best) >= ef:
                        full = True
                        d_worst = -best[0][0]
                elif d < d_worst:
                    heappush(cand, (d, u))
                    heapreplace(best, (-d, u))
                    d_worst = -best[0][0]
                elif srt:
                    break
        out = sorted((-nd, i) for nd, i in best)
        return (
            np.asarray([d for d, _ in out], dtype=np.float64),
            np.asarray([i for _, i in out], dtype=np.int64),
        )

    # ------------------------------------------------------------------
    # Algorithm 4 — heuristic neighbor selection
    # ------------------------------------------------------------------

    def _select_neighbors(self, cand_dists, cand_ids, m):
        """Distance-diversity selection (Algorithm 4).

        One greedy pass over candidates sorted ascending, with the
        min-distance-to-selected vector materialized lazily in blocks: the
        pass usually fills its ``m`` slots within the first few dozen
        candidates, so pairwise distances are computed one examination
        window at a time (a (|selected|, block) rectangle each, plus a
        one-row refresh per in-block selection) instead of the full (c, c)
        matrix — and a window that runs dry continues into the next block
        carrying its selections, never restarting from scratch.  The
        acceptance sequence is identical to the textbook exhaustive pass.
        """
        cand_ids = np.asarray(cand_ids, dtype=np.int64)
        cand_dists = np.asarray(cand_dists)
        order = np.argsort(cand_dists, kind="stable")
        ids = cand_ids[order]
        c = ids.size
        if c <= 1:
            return ids[:m]
        dists = cand_dists[order]
        cfg = self.config
        l2 = cfg.metric == "l2"
        keep = cfg.keep_pruned
        V = self._vstack[ids]  # (c, d)
        norms = self._norms[ids] if l2 else None
        dl = dists.tolist()
        blk = max(4 * m, 64)
        selected: list[int] = []  # positions into `ids`
        pruned: list[int] = []
        lo = 0
        while lo < c and len(selected) < m:
            hi = min(lo + blk, c)
            Vb = V[lo:hi]
            if selected:
                G = V[selected] @ Vb.T  # (|selected|, hi - lo)
                if l2:
                    Db = (norms[selected][:, None] - 2.0 * G
                          + norms[lo:hi][None, :])
                else:
                    Db = -G
                mts = Db.min(axis=0)
            else:
                mts = np.full(hi - lo, np.inf)
            mtsl = mts.tolist()
            for i in range(lo, hi):
                if len(selected) >= m:
                    break
                j = i - lo
                if not selected or dl[i] < mtsl[j]:
                    selected.append(i)
                    if i + 1 < hi:
                        g = Vb[j + 1:] @ V[i]
                        if l2:
                            g *= -2.0
                            g += norms[i]
                            g += norms[i + 1: hi]
                        else:
                            np.negative(g, out=g)
                        np.minimum(mts[j + 1:], g, out=mts[j + 1:])
                        mtsl = mts.tolist()
                elif keep:
                    pruned.append(i)
            lo = hi
        if keep and len(selected) < m:
            selected.extend(pruned[: m - len(selected)])
        return ids[selected]

    # ------------------------------------------------------------------
    # Connect / prune (order-dependent, sequential within a chunk)
    # ------------------------------------------------------------------

    def _set_adjacency(self, i: int, level: int, sel: np.ndarray) -> None:
        if level == 0:
            self._adj0[i, : sel.size] = sel
            self._adj0[i, sel.size:] = -1
            self._deg0[i] = sel.size
            return
        ul = level - 1
        row = self._uslot[ul][i]
        self._uadj[ul][row, : sel.size] = sel
        self._uadj[ul][row, sel.size:] = -1
        self._udeg[ul][row] = sel.size

    def _add_reverse_edge(self, s: int, i: int, level: int) -> None:
        """Append ``i`` to s's adjacency; deferred heuristic prune.

        While the slack row has headroom the append is two scalar writes.
        Only when the row physically fills (m_max + slack entries) does the
        Algorithm-4 heuristic run, compacting back to m_max — amortizing
        the prune over ~slack appends instead of re-running it per edge on
        every saturated node (the dominant cost of the per-edge policy).
        """
        if level == 0:
            adj, deg, row, m_max = (
                self._adj0, self._deg0, s, self.config.m_max0
            )
        else:
            ul = level - 1
            row = self._uslot[ul][s]
            adj, deg, m_max = self._uadj[ul], self._udeg[ul], self.config.M
        d = deg[row]
        if d < adj.shape[1]:
            adj[row, d] = i
            deg[row] = d + 1
            return
        cand = np.empty(d + 1, dtype=np.int64)
        cand[:d] = adj[row, :d]
        cand[d] = i
        qv = self._vstack[s]
        dc = self._dist(qv, cand, float(self._norms[s]))
        sel = self._select_neighbors(dc, cand, m_max)
        adj[row, : sel.size] = sel
        adj[row, sel.size:] = -1
        deg[row] = sel.size

    def _candidates(self, q, dists, ids, level):
        """ef_construction beam results, optionally extended with the
        candidates' own neighbors (Algorithm 4's extendCandidates switch;
        np.unique order — deterministic)."""
        if not self.config.extend_candidates or ids.size == 0:
            return dists, ids
        if level == 0:
            rows = self._adj0[ids]
        else:
            ul = level - 1
            rows = self._uadj[ul][self._uslot[ul][ids]]
        ext = np.unique(rows[rows >= 0])
        ext = ext[~np.isin(ext, ids)]
        if ext.size == 0:
            return dists, ids
        d_ext = self._dist(q, ext, self._q2(q))
        return (
            np.concatenate([dists, d_ext.astype(dists.dtype)]),
            np.concatenate([ids, ext]),
        )

    def _connect(self, i: int, lvl: int, ep) -> None:
        """Phase 2 for node ``i``: ef_construction search + heuristic select
        + reverse edges with prune, at levels min(max_level, lvl) .. 0."""
        cfg = self.config
        x = self._vstack[i]
        for level in range(min(self.max_level, lvl), -1, -1):
            dists, ids = self._search_layer(x, ep, cfg.ef_construction, level)
            cand_d, cand_i = self._candidates(x, dists, ids, level)
            sel = self._select_neighbors(cand_d, cand_i, cfg.M)
            self._set_adjacency(i, level, sel)
            for s in sel.tolist():
                self._add_reverse_edge(s, i, level)
            ep = ids

    # ------------------------------------------------------------------
    # Bulk insert (the wavefront build loop)
    # ------------------------------------------------------------------

    # lanns: dims[n<=180_000_000, d<=2048, C<=65536]
    def add_batch(  # lanns: hotpath
        self,
        vectors: np.ndarray,
        keys: Optional[np.ndarray] = None,
        *,
        chunk: int = DEFAULT_BUILD_CHUNK,
    ):
        """Bulk-insert ``vectors`` (HNSW build is order-dependent).

        Points are consumed in wavefront chunks: a maximal run of up to
        ``chunk`` consecutive level-0 points gets its phase-1 greedy descent
        in ONE vectorized batched walk (``_descend``) against the frozen
        spine, then the order-dependent connect/prune phase runs
        sequentially point-by-point.  Spine points (level >= 1, a ~1/M
        fraction) are inserted fully sequentially since they mutate the
        upper levels the descent reads.  The built graph is bit-identical
        for any ``chunk`` >= 1 and across ``add_batch`` call splits.
        """
        cfg = self.config
        if chunk < 1:
            raise ValueError(f"chunk={chunk} — expected >= 1")
        vectors = np.asarray(vectors, dtype=np.float32)
        if cfg.metric == "cos":
            vectors = _normalize_rows(vectors)
        n_new = vectors.shape[0]
        if keys is not None:
            keys = np.asarray(keys)
            if keys.shape[0] != n_new:
                raise ValueError(
                    f"keys length {keys.shape[0]} != vectors {n_new}"
                )
            self.keys = (
                keys if self.keys is None
                else np.concatenate([self.keys, keys])
            )
        if n_new == 0:
            return self
        base = self._n
        self._ensure_capacity(base + n_new)
        self._n = base + n_new
        self._vstack[base: base + n_new] = vectors
        self._norms[base: base + n_new] = np.einsum(
            "nd,nd->n", vectors, vectors
        )
        # batched level draws: element-wise identical to per-point .random()
        # draws from the same generator state, so call-split boundaries do
        # not move the level sequence.
        u = self._rng.random(n_new)
        lvls = np.minimum(
            (-np.log(np.maximum(u, 1e-12)) * cfg.m_l).astype(np.int64),
            cfg.max_level_cap,
        ).astype(np.int32)
        self._levels[base: base + n_new] = lvls

        r = 0
        while r < n_new:
            i = base + r
            lvl = int(lvls[r])
            if self.entry < 0:
                # very first point: becomes the entry at its drawn level
                self._register_upper(i, lvl)
                self.entry = i
                self.max_level = lvl
                r += 1
                continue
            if lvl == 0:
                r_end = r + 1
                while (
                    r_end < n_new
                    and lvls[r_end] == 0
                    and r_end - r < chunk
                ):
                    r_end += 1
                eps, _ = self._descend(
                    vectors[r:r_end],
                    np.zeros(r_end - r, dtype=np.int32),
                )
                for j, ep in enumerate(eps.tolist()):
                    self._connect(base + r + j, 0, [ep])
                r = r_end
            else:
                self._register_upper(i, lvl)
                eps, _ = self._descend(
                    vectors[r: r + 1], np.asarray([lvl], dtype=np.int32)
                )
                self._connect(i, lvl, [int(eps[0])])
                if lvl > self.max_level:
                    self.max_level = lvl
                    self.entry = i
                r += 1
        self._frozen = None
        return self

    # ------------------------------------------------------------------
    # Freeze to arrays
    # ------------------------------------------------------------------

    def freeze(self) -> "FrozenHNSW":
        """Snapshot to frozen arrays; slack rows still above m_max get one
        final heuristic prune down to the frozen width.  Operates on copies
        — build state is untouched, so interleaving freeze() with further
        ``add_batch`` calls cannot perturb the graph."""
        if self._frozen is not None:
            return self._frozen
        cfg = self.config
        n = self._n
        m0 = cfg.m_max0
        M = cfg.M
        deg0 = self._deg0[:n]
        adj0 = np.full((n, m0), -1, dtype=np.int32)
        ok = np.flatnonzero(deg0 <= m0)
        adj0[ok] = self._adj0[ok, :m0]
        for s in np.flatnonzero(deg0 > m0).tolist():
            cand = self._adj0[s, : deg0[s]].astype(np.int64)
            dc = self._dist(self._vstack[s], cand, float(self._norms[s]))
            sel = self._select_neighbors(dc, cand, m0)
            adj0[s, : sel.size] = sel
        n_upper = len(self._uadj)
        upper_adj = np.full((n_upper, n, M), -1, dtype=np.int32)
        for ul in range(n_upper):
            slot = self._uslot[ul][:n]
            nodes = np.flatnonzero(slot >= 0)
            rows = slot[nodes]
            deg = self._udeg[ul][rows]
            src = self._uadj[ul][rows]
            sub = np.full((nodes.size, M), -1, dtype=np.int32)
            okm = deg <= M
            sub[okm] = src[okm, :M]
            for j in np.flatnonzero(~okm).tolist():
                s = int(nodes[j])
                cand = src[j, : deg[j]].astype(np.int64)
                dc = self._dist(self._vstack[s], cand, float(self._norms[s]))
                sel = self._select_neighbors(dc, cand, M)
                sub[j, : sel.size] = sel
            upper_adj[ul, nodes] = sub
        self._frozen = FrozenHNSW(
            config=cfg,
            vectors=self._vstack[:n].copy(),
            levels=self._levels[:n].copy(),
            adj0=adj0,
            upper_adj=upper_adj,
            entry=self.entry,
            keys=self.keys,
        )
        return self._frozen

    # convenience: numpy reference search (exact same algorithm as build
    # beam), over the FROZEN graph — the serving artifact — so its results
    # are comparable with the jax path bit-for-bit modulo tie-breaks.
    def search_np(self, queries: np.ndarray, k: int, ef: Optional[int] = None):
        cfg = self.config
        ef = max(ef or cfg.ef_search, k)
        queries = np.asarray(queries, dtype=np.float32)
        if cfg.metric == "cos":
            queries = _normalize_rows(queries)
        B = len(queries)
        out_d = np.full((B, k), _INF, dtype=np.float32)
        out_i = np.full((B, k), -1, dtype=np.int64)
        if self._n == 0 or B == 0:
            return out_d, out_i
        frozen = self.freeze()
        eps, _ = self._descend(
            queries, np.zeros(B, dtype=np.int32), upper=frozen.upper_adj
        )
        for qi, q in enumerate(queries):
            dists, ids = self._search_layer(
                q, [int(eps[qi])], ef, 0, adj0=frozen.adj0
            )
            m = min(k, len(ids))
            out_d[qi, :m] = dists[:m]
            out_i[qi, :m] = ids[:m]
        if self.keys is not None:
            valid = out_i >= 0
            out_i = np.where(valid, self.keys[np.clip(out_i, 0, None)], -1)
        return out_d, out_i


class HNSWIndexLegacy:
    """The pre-wavefront sequential builder (python dict adjacency + heapq).

    Kept as the before/after baseline for ``bench_build_query_scaling`` and
    as the recall oracle the bulk builder is accepted against (recall@100
    within 0.01 on the bench corpus).  One adjacency representation during
    build — ``_adj[level]`` is a dict node -> neighbor list — normalized to
    flat arrays once, at ``freeze``.
    """

    def __init__(self, config: HNSWConfig, dim: int):
        self.config = config
        self.dim = dim
        self._vecs: list[np.ndarray] = []
        self._levels: list[int] = []
        self._adj: list[dict[int, list[int]]] = []  # [level][node] -> nbrs
        self.entry: int = -1
        self.max_level: int = -1
        self._rng = np.random.default_rng(config.seed)
        self._frozen = None
        self._vstack: Optional[np.ndarray] = None
        self._visited = np.zeros(0, dtype=np.int64)
        self._visit_gen = 0
        self.keys: Optional[np.ndarray] = None

    @property
    def size(self) -> int:
        return len(self._vecs)

    def _dist(self, q, ids):
        ids = np.asarray(ids)
        vecs = self._vstack[ids]
        if self.config.metric == "l2":
            return self._norms[ids] - 2.0 * (vecs @ q) + q @ q
        return -(vecs @ q)

    def _draw_level(self) -> int:
        u = self._rng.random()
        lvl = int(-math.log(max(u, 1e-12)) * self.config.m_l)
        return min(lvl, self.config.max_level_cap)

    def _search_layer(self, q, entry_points, ef, level):
        visited = self._visited
        self._visit_gen += 1
        gen = self._visit_gen
        adj = self._adj[level]

        eps = list(dict.fromkeys(entry_points))
        d0 = self._dist(q, eps)
        cand: list[tuple[float, int]] = []
        best: list[tuple[float, int]] = []
        for d, e in zip(d0, eps):
            visited[e] = gen
            heapq.heappush(cand, (float(d), e))
            heapq.heappush(best, (-float(d), e))
        while len(best) > ef:
            heapq.heappop(best)
        while cand:
            d_c, c = heapq.heappop(cand)
            d_worst = -best[0][0]
            if d_c > d_worst and len(best) >= ef:
                break
            nbrs = [u for u in adj[c] if visited[u] != gen]
            if not nbrs:
                continue
            for u in nbrs:
                visited[u] = gen
            dn = self._dist(q, nbrs)
            for d, u in zip(dn, nbrs):
                d = float(d)
                if len(best) < ef or d < -best[0][0]:
                    heapq.heappush(cand, (d, u))
                    heapq.heappush(best, (-d, u))
                    if len(best) > ef:
                        heapq.heappop(best)
        out = sorted((-nd, i) for nd, i in best)
        return [d for d, _ in out], [i for _, i in out]

    def _select_neighbors(self, cand_dists, cand_ids, m):
        cfg = self.config
        cand_ids = np.asarray(cand_ids)
        cand_dists = np.asarray(cand_dists)
        order = np.argsort(cand_dists, kind="stable")
        ids = cand_ids[order]
        dists = cand_dists[order]
        c = len(ids)
        if c <= 1:
            return list(ids[:m])
        V = self._vstack[ids]
        if cfg.metric == "l2":
            norms = np.einsum("cd,cd->c", V, V)
            D = norms[:, None] - 2.0 * (V @ V.T) + norms[None, :]
        else:
            D = -(V @ V.T)
        selected: list[int] = []
        pruned: list[int] = []
        for i in range(c):
            if len(selected) >= m:
                break
            if not selected or dists[i] < D[i, selected].min():
                selected.append(i)
            elif cfg.keep_pruned:
                pruned.append(i)
        if cfg.keep_pruned and len(selected) < m:
            selected.extend(pruned[: m - len(selected)])
        return [int(ids[i]) for i in selected]

    def _prune_node(self, node, level, m_max):
        adj = self._adj[level][node]
        if len(adj) <= m_max:
            return
        q = self._vecs[node]
        d = self._dist(q, adj)
        self._adj[level][node] = self._select_neighbors(
            list(d), list(adj), m_max
        )

    def add_batch(self, vectors, keys=None):
        cfg = self.config
        vectors = np.asarray(vectors, dtype=np.float32)
        if cfg.metric == "cos":
            vectors = _normalize_rows(vectors)
        n_new = vectors.shape[0]
        n_total = self.size + n_new
        self._visited = np.zeros(n_total, dtype=np.int64)
        self._visit_gen = 0
        if self.size:
            self._vstack = np.concatenate([np.stack(self._vecs), vectors])
        else:
            self._vstack = vectors
        self._norms = np.einsum("nd,nd->n", self._vstack, self._vstack)

        for r in range(n_new):
            x = vectors[r]
            i = self.size
            self._vecs.append(x)
            lvl = self._draw_level()
            self._levels.append(lvl)
            while len(self._adj) <= lvl:
                self._adj.append({})
            for level in range(lvl + 1):
                self._adj[level][i] = []
            if self.entry < 0:
                self.entry = i
                self.max_level = lvl
                continue
            ep = [self.entry]
            for level in range(self.max_level, lvl, -1):
                _, ids = self._search_layer(x, ep, 1, level)
                ep = ids[:1]
            for level in range(min(self.max_level, lvl), -1, -1):
                m_max = cfg.m_max0 if level == 0 else cfg.M
                dists, ids = self._search_layer(
                    x, ep, cfg.ef_construction, level
                )
                sel = self._select_neighbors(dists, ids, cfg.M)
                self._adj[level][i] = list(sel)
                for s in sel:
                    self._adj[level][s].append(i)
                    self._prune_node(s, level, m_max)
                ep = ids
            if lvl > self.max_level:
                self.max_level = lvl
                self.entry = i
        if keys is not None:
            keys = np.asarray(keys)
            self.keys = (
                keys if self.keys is None
                else np.concatenate([self.keys, keys])
            )
        self._frozen = None
        return self

    def freeze(self) -> "FrozenHNSW":
        if self._frozen is not None:
            return self._frozen
        cfg = self.config
        n = self.size
        adj0 = np.full((n, cfg.m_max0), -1, dtype=np.int32)
        for i, nbrs in sorted(self._adj[0].items()):
            k = min(len(nbrs), cfg.m_max0)
            adj0[i, :k] = nbrs[:k]
        n_upper = max(len(self._adj) - 1, 0)
        upper_adj = np.full((n_upper, n, cfg.M), -1, dtype=np.int32)
        for level in range(1, len(self._adj)):
            for i, nbrs in sorted(self._adj[level].items()):
                nbrs = nbrs[: cfg.M]
                upper_adj[level - 1, i, : len(nbrs)] = nbrs
        self._frozen = FrozenHNSW(
            config=cfg,
            vectors=np.stack(self._vecs).astype(np.float32),
            levels=np.asarray(self._levels, dtype=np.int32),
            adj0=adj0,
            upper_adj=upper_adj,
            entry=self.entry,
            keys=self.keys,
        )
        return self._frozen


def stack_upper_adj(
    level_nodes: list, level_adj: list, n: int, M: int
) -> np.ndarray:
    """Convert the legacy ragged (level_nodes, level_adj) lists to the
    stacked (L, n, M) global-id adjacency (used when loading old artifacts)."""
    L = len(level_adj)
    upper = np.full((L, n, M), -1, dtype=np.int32)
    for l in range(L):
        ids = np.asarray(level_nodes[l], dtype=np.int64)
        a = np.asarray(level_adj[l], dtype=np.int32)
        m = min(a.shape[1], M) if a.size else 0
        if len(ids):
            upper[l, ids, :m] = a[:, :m]
    return upper


@dataclasses.dataclass
class FrozenHNSW:
    """Immutable array-form HNSW, ready for jit search / serialization."""

    config: HNSWConfig
    vectors: np.ndarray
    levels: np.ndarray
    adj0: np.ndarray
    upper_adj: np.ndarray  # (L, n, M) global-id adjacency, -1 padded
    entry: int
    keys: Optional[np.ndarray] = None

    def __post_init__(self):
        self._device_cache: dict = {}

    @property
    def size(self) -> int:
        return self.vectors.shape[0]

    @property
    def num_upper_levels(self) -> int:
        return self.upper_adj.shape[0]

    def device_arrays(self, n_pad: Optional[int] = None,
                      l_pad: Optional[int] = None, *, cached: bool = True):
        """The pytree consumed by ``beam_search`` (device-resident state).

        ``n_pad``/``l_pad`` pad the corpus rows / upper-level count to shared
        bucket sizes so beam_search traces are reused across partitions
        (padding rows are -1 adjacency = unreachable, zero vectors = never
        scored).  The pytree is built and uploaded ONCE per (n_pad, l_pad)
        bucket and cached on the index — serving must never re-ship the graph
        host->device per call.
        """
        n = self.size
        n_pad = n if n_pad is None else n_pad
        l_pad = self.num_upper_levels if l_pad is None else l_pad
        if n_pad < n or l_pad < self.num_upper_levels:
            raise ValueError(
                f"pad ({n_pad}, {l_pad}) smaller than index "
                f"({n}, {self.num_upper_levels})"
            )
        key = (n_pad, l_pad)
        if cached and key in self._device_cache:
            return self._device_cache[key]
        from repro.common.utils import pad_axis_to, pad_to

        vecs = pad_to(self.vectors, n_pad)
        adj0 = pad_to(self.adj0, n_pad, fill=-1)
        upper = pad_axis_to(self.upper_adj, 1, n_pad, fill=-1)
        upper = pad_to(upper, l_pad, fill=-1)
        arrs = {
            "vectors": jnp.asarray(vecs),
            "adj0": jnp.asarray(adj0),
            "upper_adj": jnp.asarray(upper),
            "entry": jnp.asarray(self.entry, dtype=jnp.int32),
        }
        if cached:
            self._device_cache[key] = arrs
        return arrs

    # lanns: dims[B<=4096, k<=200, n<=33_554_432]
    def search(  # lanns: hotpath
        self,
        queries,
        k: int,
        ef: Optional[int] = None,
        max_iters: int = 0,
        *,
        n_pad: Optional[int] = None,
        l_pad: Optional[int] = None,
        cached: bool = True,
        pad_queries: bool = True,
    ):
        """Batched jit beam search. Returns (dists (B,k), ids (B,k)).

        pad_queries=True pads the batch to its quarter-pow2 bucket (see
        ``next_pow2_quarter``: <= 25% padding, ~4 buckets per octave) so
        routed subsets of every size reuse a bounded set of traces.
        cached=False rebuilds the device pytree per call (the
        pre-device-resident behaviour; kept for before/after benchmarking).
        """
        cfg = self.config
        ef = max(ef or cfg.ef_search, k)
        if max_iters <= 0:
            max_iters = ef + 2 * cfg.M
        q = np.asarray(queries, dtype=np.float32)
        B = q.shape[0]
        if B == 0:
            return (np.full((0, k), _INF, np.float32),
                    np.full((0, k), -1, np.int64))
        if cfg.metric == "cos":
            q = q / np.maximum(
                np.linalg.norm(q, axis=-1, keepdims=True), 1e-12
            )
        valid = None
        if pad_queries:
            from repro.common.utils import next_pow2_quarter, pad_to

            B_pad = next_pow2_quarter(B)
            if B_pad != B:
                q = pad_to(q, B_pad)
                valid = jnp.asarray(np.arange(B_pad) < B)
        arrs = self.device_arrays(n_pad, l_pad, cached=cached)
        d, i = beam_search(  # lanns: noqa[LANNS033] -- k ranges over the finite per-request knob set (<= 200), not the corpus; bounded trace cardinality by the knob_groups contract
            arrs,
            jnp.asarray(q),
            valid,
            k=k,
            ef=ef,
            max_iters=max_iters,
            metric="l2" if cfg.metric == "l2" else "ip",
        )
        d, i = np.asarray(d)[:B], np.asarray(i)[:B]  # lanns: noqa[LANNS003] -- the single designed host sync of the beam batch
        if self.keys is not None:
            valid = i >= 0
            i = np.where(valid, self.keys[np.clip(i, 0, None)], -1)
        return d, i


# ---------------------------------------------------------------------------
# JAX search (serving hot path)
# ---------------------------------------------------------------------------


#: full f32 dots on the device (a TPU's default is one bf16 pass)
_F32 = jax.lax.Precision.HIGHEST


def _distance_rows(metric, q, x):
    """q (d,), x (m, d) -> (m,). Lower is better."""
    if metric == "l2":
        # ||q-x||^2 = ||x||^2 - 2<q,x> + ||q||^2 ; the ||q||^2 term is a
        # per-query constant and irrelevant for ranking but we keep it so the
        # returned distances are true squared distances (tests rely on it).
        return jnp.sum((x - q[None, :]) ** 2, axis=-1)
    return -jnp.matmul(x, q, precision=_F32)


def _make_row_dist(arrs, metric):
    """Per-lane distance closure: (q, rows) -> (m,) scores, lower is better.

    fp32 mode (no ``norms2`` leaf in ``arrs``): gather fp32 rows, exact
    ``_distance_rows`` — the pre-existing path, op-for-op.

    Quantized mode (``arrs['norms2']`` present): ``vectors`` holds int8
    CODES and the caller pre-folds the partition's per-dim scales into each
    lane's query (``q_lane = q * scales[partition]``), so one fp32 cast-gemm
    per gather gives ``<q, x_hat>`` — the dot against the dequantized row —
    with no per-row scale gather.  'l2' scores are then
    ``||x_hat||^2 - 2<q, x_hat>``: the true squared distance to the
    dequantized point MINUS the per-query ||q||^2 constant, which cannot
    change any within-lane ordering (the beam only ever compares distances
    of one lane); the exact re-rank stage replaces these scores anyway.
    Presence of the extra pytree leaf changes the jit cache key, so fp32
    traces are never polluted.
    """
    vectors = arrs["vectors"]
    norms2 = arrs.get("norms2")
    if norms2 is None:
        return lambda q, rows: _distance_rows(metric, q, vectors[rows])

    def dist(q, rows):
        dots = jnp.matmul(vectors[rows].astype(jnp.float32), q, precision=_F32)
        if metric == "l2":
            return norms2[rows] - 2.0 * dots
        return -dots

    return dist


def _beam_search_lanes(arrs, queries, entry_rows, offsets, valid, *,
                       k, ef, max_iters, metric):
    """The beam-search core, in flat row space.

    Upper levels: greedy descent (while_loop) over the stacked (L, n, M)
    row-indexed adjacency — a padding level (all -1 rows) is a no-op walk, so
    partitions with fewer levels share the trace of the deepest one.  Level 0:
    best-first beam of width ``ef`` kept as dense arrays; each iteration
    expands the best unexpanded entry.  All ops are fixed-shape so the whole
    thing jits, vmaps over lanes, and shard_maps.  Expanded-set semantics: a
    node evicted from the beam may be re-inserted and re-expanded later; this
    wastes a little compute but never hurts correctness (matches the
    `visited`-free formulations of array HNSW).

    Each lane walks rows [off, off + n_partition) of the flat arrays:
    adjacency entries are partition-local, so every gathered neighbor id is
    shifted by the lane's ``off``.  A single partition is the off == 0
    special case.  An invalid lane (padding) seeds the walk with a -inf
    entry distance and an empty beam, so both loops exit immediately.

    ``arrs`` may carry a quantized corpus (int8 codes + ``norms2``; see
    ``_make_row_dist``) — the walk itself is precision-agnostic.
    """
    adj0 = arrs["adj0"]
    upper_adj = arrs["upper_adj"]
    num_upper_levels = upper_adj.shape[0]
    row_dist = _make_row_dist(arrs, metric)

    def one_lane(q, ep, off, v):
        def to_rows(nbrs):
            return jnp.where(nbrs >= 0, nbrs + off, -1)

        # ---- upper levels: greedy walk to a local minimum per level
        ep_d = row_dist(q, jnp.clip(ep, 0)[None])[0]
        ep_d = jnp.where(v, ep_d, -jnp.inf)
        ep = jnp.where(v, ep, -1)
        for l in range(num_upper_levels - 1, -1, -1):
            adj = upper_adj[l]

            def body(state):
                ep, ep_d, _ = state
                nbrs = to_rows(adj[jnp.clip(ep, 0)])
                valid_n = nbrs >= 0
                nd = row_dist(q, jnp.clip(nbrs, 0))
                nd = jnp.where(valid_n, nd, jnp.inf)
                j = jnp.argmin(nd)
                better = nd[j] < ep_d
                return (
                    jnp.where(better, nbrs[j], ep),
                    jnp.where(better, nd[j], ep_d),
                    better,
                )

            def cond(state):
                return state[2]

            ep, ep_d, _ = jax.lax.while_loop(cond, body, (ep, ep_d, jnp.bool_(True)))

        # ---- level 0 beam
        m0 = adj0.shape[1]
        beam_ids = jnp.full((ef,), -1, dtype=jnp.int32).at[0].set(ep)
        beam_d = jnp.full((ef,), jnp.inf, dtype=jnp.float32).at[0].set(ep_d)
        beam_exp = jnp.zeros((ef,), dtype=jnp.bool_)

        def cond(state):
            beam_ids, beam_d, beam_exp, it = state
            frontier = (~beam_exp) & (beam_ids >= 0)
            return jnp.any(frontier) & (it < max_iters)

        def body(state):
            beam_ids, beam_d, beam_exp, it = state
            pick_d = jnp.where((~beam_exp) & (beam_ids >= 0), beam_d, jnp.inf)
            b = jnp.argmin(pick_d)
            beam_exp = beam_exp.at[b].set(True)
            node = beam_ids[b]
            nbrs = to_rows(adj0[jnp.clip(node, 0)])
            valid_n = nbrs >= 0
            # dedup against current beam (m0 x ef comparison matrix)
            dup = jnp.any(nbrs[:, None] == beam_ids[None, :], axis=1)
            valid_n = valid_n & (~dup)
            nd = row_dist(q, jnp.clip(nbrs, 0))
            nd = jnp.where(valid_n, nd, jnp.inf)
            # merge (ef + m0) candidates, keep best ef
            all_ids = jnp.concatenate([beam_ids, jnp.where(valid_n, nbrs, -1)])
            all_d = jnp.concatenate([beam_d, nd])
            all_exp = jnp.concatenate([beam_exp, jnp.zeros((m0,), jnp.bool_)])
            neg_top, idx = jax.lax.top_k(-all_d, ef)
            return all_ids[idx], -neg_top, all_exp[idx], it + 1

        beam_ids, beam_d, beam_exp, _ = jax.lax.while_loop(
            cond, body, (beam_ids, beam_d, beam_exp, jnp.int32(0))
        )
        neg_top, idx = jax.lax.top_k(-beam_d, k)
        return -neg_top, beam_ids[idx]

    return jax.vmap(one_lane)(queries, entry_rows, offsets, valid)


def _beam_search_impl(arrs, queries, valid=None, *, k, ef, max_iters, metric):
    """Single-partition batched search: the zero-offset case of the core."""
    B = queries.shape[0]
    if valid is None:
        valid = jnp.ones((B,), dtype=jnp.bool_)
    entry_rows = jnp.broadcast_to(
        jnp.asarray(arrs["entry"], jnp.int32), (B,)
    )
    offsets = jnp.zeros((B,), jnp.int32)
    return _beam_search_lanes(
        {k_: arrs[k_] for k_ in ("vectors", "adj0", "upper_adj")},
        queries, entry_rows, offsets, valid,
        k=k, ef=ef, max_iters=max_iters, metric=metric,
    )


@partial(jax.jit, static_argnames=("k", "ef", "max_iters", "metric"))
def beam_search(arrs, queries, valid=None, *, k, ef, max_iters, metric):
    """Jit entry point: one partition, queries (B, d) -> ((B, k), (B, k)).
    ``valid`` (B,) marks real rows of a padded batch; padding rows exit
    immediately instead of walking the graph."""
    return _beam_search_impl(
        arrs, queries, valid, k=k, ef=ef, max_iters=max_iters, metric=metric
    )


@partial(jax.jit, static_argnames=("k", "ef", "max_iters", "metric"))
def beam_search_flat(arrs, queries, entry_rows, offsets, valid, *,
                     k, ef, max_iters, metric):
    """Multi-partition search over FLATTENED partition arrays.

    ``arrs`` holds every partition's rows concatenated: vectors (P*n, d),
    adj0 (P*n, 2M), upper_adj (L, P*n, M); adjacency entries stay partition-
    LOCAL.  Each lane of ``queries`` (T, d) carries its partition via
    ``offsets`` (T,) — the partition's first row in the flat arrays — and
    starts at ``entry_rows`` (T,) (the partition entry point, already
    offset).  Gathered neighbor ids are shifted by the lane's offset, so the
    whole walk runs in global row space and one vmapped call serves an
    arbitrary mix of (partition, query) pairs.

    vs the dense (P, C) ``beam_search_stacked``: lane count is the NUMBER OF
    ROUTED PAIRS (padded to a bucket), not partitions x the most-loaded
    partition's count — under unbalanced routing the dense form wastes up to
    ~2x lanes, and under vmap every padded lane runs the full loop.  Returns
    (dists (T, k), rows (T, k)) with rows in global (flat) space; map them
    through a flat key table host-side.

    Quantized corpora: pass int8 codes as ``vectors`` plus a ``norms2``
    leaf and pre-fold each lane's per-partition scales into its query row
    (``_make_row_dist``); the extra leaf keys a separate jit trace, so the
    fp32 path is untouched.
    """
    return _beam_search_lanes(
        arrs, queries, entry_rows, offsets, valid,
        k=k, ef=ef, max_iters=max_iters, metric=metric,
    )


@partial(jax.jit, static_argnames=("k", "ef", "max_iters", "metric"))
def beam_search_stacked(arrs, queries, valid=None, *, k, ef, max_iters, metric):
    """Multi-partition search: every leaf of ``arrs`` carries a leading
    partition axis (vectors (P, n, d), adj0 (P, n, 2M), upper_adj
    (P, L, n, M), entry (P,)) and queries is (P, C, d) — one vmapped
    ``beam_search`` serves all (shard, segment) partitions in a single call,
    with no per-partition Python dispatch or host<->device sync.  ``valid``
    (P, C) marks real query slots; padding slots short-circuit.
    """
    if valid is None:
        valid = jnp.ones(queries.shape[:-1], dtype=jnp.bool_)
    return jax.vmap(
        lambda a, q, v: _beam_search_impl(
            a, q, v, k=k, ef=ef, max_iters=max_iters, metric=metric
        )
    )(arrs, queries, valid)
