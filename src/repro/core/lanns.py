"""LannsIndex — the end-to-end LANNS platform object (paper §5).

Composes the pieces exactly as the paper's offline framework does:

  1. ``fit``: learn ONE segmenter on a uniform subsample (§5.1) — shared by
     every shard, stored once.
  2. ``build``: two-level partition (hash shard → segment), then build an
     independent per-(shard, segment) engine **in parallel** (§5.2).  Engines:
     'hnsw' (the paper's choice) or 'scan' (TPU-native dense Pallas scan —
     DESIGN.md §2).  Builds are resumable: each partition artifact is written
     atomically with a manifest, so a preempted build restarts where it died
     (the paper's HDFS-temp-path fault-tolerance story, §5.3.1).
  3. ``query``: route queries (virtual spill), search only routed segments,
     segment-merge inside the shard, shard-merge at the broker with
     perShardTopK trimming (§5.3.2).

The distributed on-mesh serving path lives in repro/serve/retrieval.py; this
module is the offline/reference implementation that the paper benchmarks in
Tables 1-7 and that our benchmark harness mirrors.
"""

from __future__ import annotations

import dataclasses
import json
import multiprocessing
import os
import tempfile
import time
from concurrent.futures import ProcessPoolExecutor
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.utils import (
    Timer,
    next_pow2,
    next_pow2_quarter,
)
from repro.core.hnsw import DEFAULT_BUILD_CHUNK, HNSWConfig, HNSWIndex
from repro.core.merge import per_shard_topk
from repro.core.plan import (
    QueryPlanExecutor,
    choose_merge_path,
    knob_groups,
    query_stats,
)
from repro.core.segmenter import SegmenterConfig
from repro.core.sharding import TwoLevelPartitioner
from repro.kernels import ops
from repro.obs.telemetry import DETACHED

# Scale-safety contract (repro.analysis.scalecheck): paper-scale bounds —
# batches to 4096 queries, per-request topk <= 200, up to 4096 partitions
# of up to 2^25 pow2-padded rows each.
# lanns: dims[B<=4096, k<=200, P<=4096, n_pad<=33_554_432]

#: flattened ids (partition row offsets, adjacency entries) live on an
#: int32 device lattice; every `pi * n_pad + row` must stay below this
_INT32_MAX = np.iinfo(np.int32).max


@dataclasses.dataclass(frozen=True)
class LannsConfig:
    """(n, m)-partitioning in the paper's notation: n shards x m segments.

    metric: 'l2' | 'ip' | 'cos' | 'mips'.  'mips' (beyond-paper) applies the
    augmented-vector reduction [Bachrach et al., RecSys'14]: corpus rows get
    an extra coordinate sqrt(M^2 - |x|^2) (queries get 0), turning max-inner-
    product into L2 NN — which is what hyperplane segmenters route well
    (raw-IP routing loses the norm component entirely).  Returned distances
    are converted back to inner products (negated, lower-is-better).

    quantized: 'none' | 'q8' — 'q8' serves partitions from int8 codes with
    an exact fp32 re-rank, cutting the resident corpus ~4x with
    near-identical recall.  Composes with BOTH engines: 'scan' runs the
    two-stage int8 scan (candidates = ``rerank_factor * perShardTopK`` per
    routed lane), 'hnsw' runs the quantized beam (graph walk over int8
    codes, then the same shared exact re-rank stage).
    rerank_store: where the exact fp32 originals live for stage 2 —
    'host' (numpy / mmap-friendly), 'device', or 'auto' (host on CPU,
    device on TPU).
    """

    num_shards: int = 1
    num_segments: int = 8
    segmenter: str = "rh"  # 'rs' | 'rh' | 'apd'
    alpha: float = 0.15
    spill: str = "virtual"  # 'virtual' | 'physical'
    metric: str = "l2"
    engine: str = "hnsw"  # 'hnsw' | 'scan'
    hnsw_m: int = 16
    ef_construction: int = 100
    ef_search: int = 100
    topk_confidence: float = 0.95
    seed: int = 0
    segmenter_sample: int = 250_000
    quantized: str = "none"  # 'none' | 'q8'
    rerank_factor: int = 2
    rerank_store: str = "auto"  # 'auto' | 'host' | 'device'

    def segmenter_config(self) -> SegmenterConfig:
        return SegmenterConfig(
            kind=self.segmenter,
            num_segments=self.num_segments,
            alpha=self.alpha,
            spill=self.spill,
            seed=self.seed,
            sample_size=self.segmenter_sample,
        )

    def hnsw_config(self) -> HNSWConfig:
        return HNSWConfig(
            M=self.hnsw_m,
            ef_construction=self.ef_construction,
            ef_search=self.ef_search,
            metric="l2" if self.metric == "mips" else self.metric,
            seed=self.seed,
        )


def _build_one_partition(args):
    """Worker: build one (shard, segment) engine.  Top-level for pickling."""
    (s, g, vectors, keys, engine, hnsw_cfg, chunk) = args
    t0 = time.perf_counter()
    if engine == "hnsw" and len(vectors) > 0:
        idx = HNSWIndex(hnsw_cfg, vectors.shape[1])
        idx.add_batch(vectors, keys, chunk=chunk)
        frozen = idx.freeze()
        payload = {
            "kind": "hnsw",
            "vectors": frozen.vectors,
            "levels": frozen.levels,
            "adj0": frozen.adj0,
            "entry": frozen.entry,
            "keys": frozen.keys,
            "upper_adj": frozen.upper_adj,
        }
    else:
        payload = {"kind": "scan", "vectors": vectors, "keys": keys}
    return s, g, payload, time.perf_counter() - t0


def _summarize_seconds(secs: list) -> dict:
    """Compact build-cost summary persisted in manifests in place of the
    raw per-partition timing dict (which scales with partition count)."""
    if not secs:
        return {}
    return {
        "min": float(np.min(secs)),
        "median": float(np.median(secs)),
        "max": float(np.max(secs)),
        "total": float(np.sum(secs)),
        "count": len(secs),
    }


def _merge_seconds_summary(prior: dict, cur: dict) -> dict:
    """min/max/total/count merge exactly across build runs; the merged
    median is count-weighted (raw times are deliberately not persisted)."""
    if not prior or not prior.get("count"):
        return cur
    if not cur or not cur.get("count"):
        return prior
    n0, n1 = prior["count"], cur["count"]
    return {
        "min": min(prior["min"], cur["min"]),
        "median": (prior["median"] * n0 + cur["median"] * n1) / (n0 + n1),
        "max": max(prior["max"], cur["max"]),
        "total": prior["total"] + cur["total"],
        "count": n0 + n1,
    }


#: width of the corpus slabs the scan uploads: the device's lane width
_SLAB = 128


def _scan_dispatch(
    queries: np.ndarray,
    vectors: np.ndarray,
    k: int,
    metric: str,
    n_valid: Optional[int] = None,
    spans=DETACHED,
):
    """Queue one fused distance+top-k call over a routed query batch.

    Goes through ``ops.distance_topk`` (Pallas kernel on TPU, blocked jnp
    scan elsewhere).  The batch is padded to the next power of two AND the
    corpus arrives padded to a shared pow2 size bucket (``n_valid`` real
    rows), so the executor's per-(shard, segment) calls reuse a bounded set
    of jit traces — O(log B x log N buckets) — instead of retracing for
    every (routed-subset size, partition size) pair.

    The host->device copy of queries and corpus is made here, in the
    ``scan.upload`` span of ``spans`` (an ``obs.PlanSpans``, which waits
    for the copy to land), and counts its bytes; the scan counts its path
    (``ops.scan_path``).  Returns the padded answers as device arrays, not
    yet fetched: ``_scan_collect`` fetches them, so the caller can queue
    the next partition's upload first.  Detached, nothing waits and the
    kernel is queued behind the copy.
    """
    B, D = queries.shape
    B_pad = next_pow2(B)
    qp = queries
    if B_pad != B:
        qp = np.zeros((B_pad, D), np.float32)
        qp[:B] = queries
    n_rows = vectors.shape[0]
    # the corpus goes as slabs of _SLAB elements of its bytes (ops takes
    # the rows in any shape): an (N, D) upload has the runtime tile it on
    # the host piece by piece (slower, and one profiler event a piece), a
    # 1-D upload copies slower still
    slab = vectors.reshape(-1)
    if slab.size % _SLAB == 0:
        slab = slab.reshape(-1, _SLAB)
    with spans.span("scan.upload"):
        qp, slab = jax.device_put(qp), jax.device_put(slab)
        spans.ready(qp, slab)
    spans.moved("h2d", qp.nbytes + slab.nbytes)
    spans.scanned(ops.scan_path(n_rows, k))
    return ops.distance_topk(qp, slab, k, metric, n_valid=n_valid)  # lanns: noqa[LANNS033] -- k ranges over the finite per-request knob set (<= 200), capped by partition size; not corpus-dependent


def _scan_collect(answers, B: int, spans=DETACHED):
    """Fetch the answers ``_scan_dispatch`` queued, in the ``scan.wait``
    span of ``spans``, counting their bytes; sliced to the ``B`` real
    queries."""
    d, i = answers
    with spans.span("scan.wait"):
        d, i = np.asarray(d), np.asarray(i)  # lanns: noqa[LANNS003] -- the designed host sync of one routed scan, made once the next partition's scan is dispatched, so its upload overlaps this scan
    spans.moved("d2h", d.nbytes + i.nbytes)
    return d[:B], i[:B].astype(np.int64)


class _PendingScan(NamedTuple):
    """A routed fp32 scan dispatched by ``_Partition.dispatch``, not yet
    fetched: its answers on the device, the routed batch and the k asked
    for."""

    answers: tuple
    b: int
    k: int


def _pad_to_k(d: np.ndarray, i: np.ndarray, k: int):
    """Pad (B, k_eff) answers to (B, k) with (inf, -1)."""
    B, k_eff = d.shape
    if k_eff < k:
        d = np.concatenate(
            [d, np.full((B, k - k_eff), np.inf, np.float32)], axis=1
        )
        i = np.concatenate(
            [i.astype(np.int64), np.full((B, k - k_eff), -1, np.int64)],
            axis=1,
        )
    return d, i.astype(np.int64)


class _Partition:
    """A built (shard, segment) engine."""

    def __init__(self, payload, config: LannsConfig):
        self.kind = payload["kind"]
        self.config = config
        self.keys = payload.get("keys")
        self.vectors = payload["vectors"]
        self._scan_pad = None  # lazily bucketed scan corpus (pow2 rows)
        self.q8 = None
        if self.kind == "hnsw":
            from repro.core.hnsw import FrozenHNSW

            self.frozen = FrozenHNSW(
                config=config.hnsw_config(),
                vectors=payload["vectors"],
                levels=payload["levels"],
                adj0=payload["adj0"],
                upper_adj=payload["upper_adj"],
                entry=int(payload["entry"]),
                keys=payload.get("keys"),
            )
            if config.quantized == "q8" and self.size > 0:
                # quantized beam codes: frozen vectors are already
                # metric-prepped (cos rows normalized at build, mips rows
                # augmented), so encode as-is — 'ip' for cos avoids a
                # second normalization pass inside the codec.
                hm = config.hnsw_config().metric
                self.q8 = self._q8_from_payload(
                    payload, self.frozen.vectors, "l2" if hm == "l2" else "ip"
                )
        elif config.quantized == "q8" and self.size > 0:
            q8_metric = "l2" if config.metric == "mips" else config.metric
            self.q8 = self._q8_from_payload(payload, self.vectors, q8_metric)

    @staticmethod
    def _q8_from_payload(payload, vectors, q8_metric):
        from repro.quant.codec import Q8Corpus, quantize_q8

        if payload.get("q8_codes") is not None:
            return Q8Corpus(
                codes=payload["q8_codes"],
                scales=payload["q8_scales"],
                norms2=payload["q8_norms2"],
                metric=q8_metric,
            )
        # legacy fp32 artifact (or fresh build): quantization is
        # deterministic, so encoding here == encoding at save time.
        return quantize_q8(vectors, q8_metric)

    @property
    def size(self):
        return 0 if self.vectors is None else len(self.vectors)

    def scan_corpus(self):
        """Scan corpus padded to its quarter-pow2 size bucket (cached).

        Shared buckets mean ``distance_topk`` traces are reused ACROSS
        segments; padding rows are masked via n_valid, so results are
        bit-identical to scanning the raw corpus.  Quarter-pow2 steps (the
        same grid the HNSW lanes and q8 codes use) cap the padded-copy and
        padded-gemm waste at 25% while keeping the trace count logarithmic.
        """
        if self._scan_pad is None:
            n_pad = next_pow2_quarter(self.size)
            if n_pad == self.size:
                self._scan_pad = self.vectors
            else:
                pad = np.zeros((n_pad, self.vectors.shape[1]), np.float32)
                pad[: self.size] = self.vectors
                self._scan_pad = pad
                # drop the unpadded copy: the view keeps every other use
                # (save, re-rank stores) working, so the only extra resident
                # bytes are the <=25% padding rows.
                self.vectors = pad[: self.size]
        return self._scan_pad

    # lanns: hotpath
    def search(
        self,
        queries: np.ndarray,
        k: int,
        ef: Optional[int] = None,
        *,
        n_pad: Optional[int] = None,
        l_pad: Optional[int] = None,
        legacy: bool = False,
        spans=DETACHED,
    ):
        if self.size == 0:
            B = queries.shape[0]
            return (
                np.full((B, k), np.inf, np.float32),
                np.full((B, k), -1, np.int64),
            )
        if self.kind != "hnsw":
            return self.collect(
                self.dispatch(queries, k, spans=spans), spans=spans
            )
        if legacy:
            # pre-device-resident behaviour: re-upload the graph per call
            # and trace per routed-subset size (before/after benchmarks)
            d, i = self.frozen.search(
                queries, min(k, self.size), ef=ef, cached=False,
                pad_queries=False,
            )
        else:
            # full k even when size < k: the beam's (inf, -1) slots are
            # exactly the padding below, and a uniform static k keeps one
            # beam_search trace shared across unevenly-sized partitions.
            d, i = self.frozen.search(
                queries, k, ef=ef, n_pad=n_pad, l_pad=l_pad
            )
        return _pad_to_k(d, i, k)

    # lanns: hotpath
    def dispatch(self, queries: np.ndarray, k: int, *, spans=DETACHED):
        """The first half of a scan partition's ``search``: upload the
        routed queries and the corpus, queue the scan, and return it as a
        ``_PendingScan`` for ``collect``."""
        metric = "l2" if self.config.metric == "mips" else self.config.metric
        answers = _scan_dispatch(
            queries, self.scan_corpus(), min(k, self.size), metric,
            n_valid=self.size, spans=spans,
        )
        return _PendingScan(answers, queries.shape[0], k)

    # lanns: hotpath
    def collect(self, pending: _PendingScan, *, spans=DETACHED):
        """The second half: fetch a dispatched scan's answers, map them to
        global ids through ``keys``, and pad them to the k asked for."""
        d, i = _scan_collect(pending.answers, pending.b, spans)
        if self.keys is not None:
            i = np.where(i >= 0, self.keys[np.clip(i, 0, None)], -1)
        return _pad_to_k(d, i, pending.k)


class LannsIndex:
    """End-to-end LANNS index: fit -> build -> query (+ save/load/resume)."""

    def __init__(self, config: LannsConfig):
        if config.quantized not in ("none", "q8"):
            raise ValueError(
                f"quantized={config.quantized!r} — expected 'none' or 'q8'"
            )
        if config.rerank_store not in ("auto", "host", "device"):
            raise ValueError(
                f"rerank_store={config.rerank_store!r} — expected 'auto', "
                "'host' or 'device'"
            )
        self.config = config
        self.partitioner = TwoLevelPartitioner(
            config.num_shards, config.segmenter_config()
        )
        self.partitions: dict[tuple, _Partition] = {}
        self.build_stats: dict = {}
        # lazily-built stacked HNSW device pytrees, keyed by quantized flag
        self._stack: dict[bool, Optional[dict]] = {}
        self._q8_exec = None  # lazily-built two-stage quantized executor
        self._exec = QueryPlanExecutor(self)  # the staged query executor
        # optional obs.Telemetry bundle; None (default) = untimed serving
        self.telemetry = None

    def attach_telemetry(self, telemetry) -> "LannsIndex":
        """Attach (or, with None, detach) an ``obs.Telemetry`` bundle.

        Attached, the staged executor times its route/candidates/rerank/
        merge boundaries into the bundle's registry and span sink, labeled
        by engine/quantized/merge_path/pow2 batch bucket.  Detached — the
        default — the executor reads no clock at all, so results are
        bit-identical either way (asserted in tests/test_obs.py) and the
        off path carries zero overhead.
        """
        self.telemetry = telemetry
        return self

    # -- stacked HNSW serving state -------------------------------------------

    def _invalidate_stack(self):
        self._stack = {}
        self._q8_exec = None

    def _q8_executor(self):
        """Two-stage quantized scan executor over every non-empty scan
        partition (device codes upload once, cached like the HNSW stack)."""
        if self._q8_exec is None:
            from repro.quant.twostage import (
                QuantizedScanExecutor,
                _Q8Partition,
            )

            metric = (
                "l2" if self.config.metric == "mips" else self.config.metric
            )
            parts = {
                sg: _Q8Partition(p.q8, p.vectors, p.keys, metric)
                for sg, p in sorted(self.partitions.items())
                if p.kind == "scan" and p.size > 0 and p.q8 is not None
            }
            self._q8_exec = QuantizedScanExecutor(
                parts,
                metric,
                self.config.rerank_factor,
                self.config.rerank_store,
            )
        return self._q8_exec

    def _hnsw_parts(self):
        """Servable HNSW partitions, sorted by (shard, segment).

        The single source of the eligibility rule — both dispatch modes
        (stacked / partition) and the shared pad computation use it, so they
        can never disagree on which partitions the HNSW paths serve.
        """
        return sorted(
            (sg, p) for sg, p in self.partitions.items()
            if p.kind == "hnsw" and p.size > 0
        )

    def _hnsw_stack(self, quantized: bool = False):
        """Flat device pytree over every non-empty HNSW partition.

        Partition rows concatenate into shared flat arrays — vectors
        (P*n_pad, d), adj0 (P*n_pad, 2M), upper_adj (l_pad, P*n_pad, M) —
        with partition p owning rows [p*n_pad, p*n_pad + size).  One
        ``beam_search_flat`` trace then serves any mix of (partition, query)
        lanes.  Built host-side and uploaded ONCE, then cached for the life
        of the partitions.  Returns {} when the index has no HNSW partitions.

        ``quantized=True`` builds the int8-code variant for the q8 beam:
        ``vectors`` holds the codes (a quarter of the fp32 bytes resident
        on device), an extra ``norms2`` leaf carries the dequantized
        squared norms, and host-side per-partition ``scales`` (P, d) +
        ``stores`` (the shared exact-rerank stores) ride along.  The two
        variants cache independently — a q8 index never uploads fp32
        vectors at all.
        """
        key = bool(quantized)
        if self._stack.get(key) is not None:
            return self._stack[key]
        items = self._hnsw_parts()
        if not items or (quantized and items[0][1].q8 is None):
            self._stack[key] = {}
            return self._stack[key]
        P = len(items)
        n_pad, l_pad = self._hnsw_pads(items)
        if P * n_pad > _INT32_MAX:
            # adjacency entries and beam lane offsets address the flat row
            # space in int32 — past 2^31 rows the ids would silently wrap
            raise OverflowError(
                f"flat HNSW stack spans {P * n_pad} rows (P={P} x "
                f"n_pad={n_pad}) — exceeds the int32 row lattice; shard "
                "the index across hosts instead"
            )
        dim = items[0][1].frozen.vectors.shape[1]
        m0 = items[0][1].frozen.adj0.shape[1]
        M = items[0][1].frozen.upper_adj.shape[2]
        adj0 = np.full((P * n_pad, m0), -1, np.int32)
        upper = np.full((l_pad, P * n_pad, M), -1, np.int32)
        entry = np.zeros((P,), np.int32)
        keys = np.full((P * n_pad,), -1, np.int64)
        if quantized:
            vecs = np.zeros((P * n_pad, dim), np.int8)
            norms2 = np.zeros((P * n_pad,), np.float32)
            scales = np.ones((P, dim), np.float32)
        else:
            vecs = np.zeros((P * n_pad, dim), np.float32)
        for pi, (_, p) in enumerate(items):
            fr = p.frozen
            n = fr.size
            off = pi * n_pad
            if quantized:
                vecs[off: off + n] = p.q8.codes
                norms2[off: off + n] = p.q8.norms2
                scales[pi] = p.q8.scales
            else:
                vecs[off: off + n] = fr.vectors
            adj0[off: off + n] = fr.adj0
            upper[: fr.num_upper_levels, off: off + n] = fr.upper_adj
            entry[pi] = fr.entry
            keys[off: off + n] = (
                fr.keys if fr.keys is not None else np.arange(n, dtype=np.int64)
            )
        arrs = {
            "vectors": jnp.asarray(vecs),
            "adj0": jnp.asarray(adj0),
            "upper_adj": jnp.asarray(upper),
        }
        stack = {
            "arrs": arrs,
            "entry": entry,  # per-partition local entry node (host)
            "keys": keys,
            "index": {sg: pi for pi, (sg, _) in enumerate(items)},
            "n_pad": n_pad,
            "l_pad": l_pad,
        }
        if quantized:
            from repro.quant.rerank import ExactStore, resolve_store_mode

            # the extra pytree leaf keys the quantized beam's own jit trace
            arrs["norms2"] = jnp.asarray(norms2)
            stack["scales"] = scales
            stack["stores"] = [
                ExactStore(p.frozen.vectors, p.frozen.keys)
                for _, p in items
            ]
            stack["store_mode"] = resolve_store_mode(
                self.config.rerank_store
            )
        self._stack[key] = stack
        return stack

    def _hnsw_pads(self, items=None):
        """Shared (n_pad, l_pad) corpus buckets over the servable partitions."""
        if items is None:
            items = self._hnsw_parts()
        if not items:
            return None, None
        return (
            next_pow2(max(p.size for _, p in items)),
            max(p.frozen.num_upper_levels for _, p in items),
        )

    # -- build ---------------------------------------------------------------

    def fit(self, data: np.ndarray) -> "LannsIndex":
        with Timer() as t:
            self.partitioner.fit(data)
        self.build_stats["segmenter_fit_seconds"] = t.seconds
        return self

    def build(
        self,
        data: np.ndarray,
        keys: Optional[np.ndarray] = None,
        *,
        workers: int = 0,
        resume_dir: Optional[str] = None,
        chunk: int = DEFAULT_BUILD_CHUNK,
    ) -> "LannsIndex":
        """Partition + parallel per-partition index build.

        workers=0 builds in-process (deterministic single-thread); workers>0
        uses a process pool — one "executor" per partition, the paper's Spark
        model.  Workers are spawned, not forked: the parent may already hold
        an accelerator, and the workers build in numpy and never touch one.
        resume_dir enables checkpointed builds: finished partitions are
        persisted and skipped on restart.  ``chunk`` is the HNSW
        wavefront batch size (throughput knob only: the built graph is
        bit-identical for any chunk >= 1 and any worker count).
        """
        cfg = self.config
        data = np.asarray(data, dtype=np.float32)
        if cfg.metric == "mips":
            # augmented-vector MIPS->L2 reduction; see LannsConfig docstring
            norms2 = np.einsum("nd,nd->n", data, data)
            self._mips_M2 = float(norms2.max())
            aug = np.sqrt(np.maximum(self._mips_M2 - norms2, 0.0))
            data = np.concatenate([data, aug[:, None]], axis=1)
        n = data.shape[0]
        if keys is None:
            keys = np.arange(n, dtype=np.int64)
        if not self.partitioner._fitted:
            self.fit(data)
        with Timer() as t_assign:
            assignment = self.partitioner.assign(data, keys)
        jobs = []
        per_partition_seconds = {}
        for s in range(cfg.num_shards):
            for g in range(cfg.num_segments):
                rows = assignment.rows[s][g]
                if resume_dir and self._partition_done(resume_dir, s, g):
                    self.partitions[(s, g)] = self._load_partition(resume_dir, s, g)
                    continue
                jobs.append(
                    (s, g, data[rows], keys[rows], cfg.engine,
                     cfg.hnsw_config(), chunk)
                )
        with Timer() as t_build:
            if workers and len(jobs) > 1:
                with ProcessPoolExecutor(
                    max_workers=workers,
                    mp_context=multiprocessing.get_context("spawn"),
                ) as ex:
                    results = list(ex.map(_build_one_partition, jobs))
            else:
                results = [_build_one_partition(j) for j in jobs]
        for s, g, payload, secs in results:
            self.partitions[(s, g)] = _Partition(payload, cfg)
            per_partition_seconds[f"{s}/{g}"] = secs
            if resume_dir:
                self._save_partition(resume_dir, s, g, payload)
        self._invalidate_stack()
        summary = _summarize_seconds(list(per_partition_seconds.values()))
        if resume_dir:
            # resumed builds keep their build-cost provenance: fold the
            # previous runs' summary (persisted in the manifest) into this
            # run's — per-partition times themselves are not persisted.
            summary = _merge_seconds_summary(
                self._prior_seconds_summary(resume_dir), summary
            )
        self.build_stats.update(
            assign_seconds=t_assign.seconds,
            build_wall_seconds=t_build.seconds,
            per_partition_seconds=per_partition_seconds,
            per_partition_seconds_summary=summary,
            partition_sizes=assignment.partition_sizes().tolist(),
            total_stored=assignment.total_stored,
            n_input=n,
            duplication_factor=assignment.total_stored / max(n, 1),
            build_workers=workers,
            build_chunk=chunk,
        )
        return self

    @staticmethod
    def _prior_seconds_summary(resume_dir: str) -> dict:
        manifest_path = os.path.join(resume_dir, "manifest.json")
        if not os.path.exists(manifest_path):
            return {}
        try:
            with open(manifest_path) as f:
                manifest = json.load(f)
        except (OSError, ValueError):
            return {}
        stats = manifest.get("build_stats") or {}
        return stats.get("per_partition_seconds_summary") or {}

    # -- query ---------------------------------------------------------------

    def warm_traces(
        self,
        max_batch: int,
        topk: int,
        *,
        ef: Optional[int] = None,
        knobs=None,
    ) -> "LannsIndex":
        """Pre-compile the serving trace set for batches up to ``max_batch``.

        Online serving forms micro-batches of ANY size <= max_batch, and the
        executor pads routed per-segment subsets to pow2 buckets — so the
        first live traffic would otherwise pay one XLA compile per unseen
        (subset-bucket, corpus-bucket) pair, hundreds of ms each, exactly the
        latencies a p99 sweep measures.  The bucket grid makes the full set
        enumerable: one ``query`` per pow2 batch size warms routing + merge +
        the stacked-HNSW / q8 paths, and for fp32 scan partitions a direct
        per-partition sweep covers every (pow2 subset, corpus bucket) combo
        regardless of how routing happens to split the batch.

        Per-request knobs: ``topk`` (and for HNSW ``ef``) are STATIC jit
        args, so every distinct knob pair a mixed workload serves has its
        own trace set — pass the workload's mix as ``knobs`` (an iterable
        of ``(topk, ef)`` pairs; None entries mean the defaults above) and
        each pair's grid is warmed too.  Without this, the first batch
        containing an unseen knob group compiles mid-window — the exact
        first-traffic poisoning this method exists to prevent.

        Coverage caveat: the per-partition sweep is exhaustive only for the
        fp32 scan engine.  q8 and HNSW indexes get best-effort whole-batch
        warming — their per-subset buckets depend on how routing splits each
        dummy batch, so rare residual compiles remain possible on first
        live traffic (extend the sweep to those executors before gating
        their p99s).
        """
        parts = [p for p in self.partitions.values() if p.size > 0]
        if not parts or max_batch < 1:
            return self
        cfg = self.config
        dim = parts[0].vectors.shape[1]
        qdim = dim - 1 if cfg.metric == "mips" else dim
        rng = np.random.default_rng(0)
        # iterate pow2 buckets up to next_pow2(max_batch): a live batch of
        # max_batch pads to that bucket, so stopping at max_batch itself
        # would leave the TOP bucket cold for non-pow2 max_batch.
        b_top = next_pow2(max_batch)
        dummy = rng.standard_normal((b_top, qdim)).astype(np.float32)
        pairs = [(topk, ef)]
        for tk_k, ef_k in knobs or ():
            pair = (topk if tk_k is None else int(tk_k),
                    ef if ef_k is None else int(ef_k))
            if pair not in pairs:
                pairs.append(pair)
        for tk_w, ef_w in pairs:
            b = 1
            while b <= b_top:
                self.query(dummy[:b], tk_w, ef=ef_w)
                b *= 2
        if cfg.engine == "scan" and cfg.quantized == "none":
            full = dummy
            if cfg.metric == "mips":
                full = np.concatenate(
                    [dummy, np.zeros((len(dummy), 1), np.float32)], axis=1
                )
            for tk_w, ef_w in pairs:
                pstk = per_shard_topk(
                    tk_w, cfg.num_shards, cfg.topk_confidence
                )
                for p in parts:
                    b = 1
                    while b <= b_top:
                        p.search(full[:b], pstk, ef=ef_w)
                        b *= 2
        return self

    # lanns: hotpath
    def query(
        self,
        queries: np.ndarray,
        topk,
        *,
        ef=None,
        return_stats: bool = False,
        hnsw_mode: str = "stacked",  # 'stacked' | 'partition' | 'legacy'
    ):
        """Two-level partitioned search with perShardTopK (paper §5.3).

        Every query goes to every shard; within a shard it goes only to the
        segments its virtual-spill routing selects.  Execution is the staged
        plan pipeline in ``repro.core.plan``: route -> candidates (fp32
        scan | q8 scan | hnsw beam | q8 hnsw beam) -> exact re-rank for the
        quantized paths -> merge (dedup-free or two-level, decided in ONE
        place by ``choose_merge_path``).

        Per-request knobs: ``topk`` and ``ef`` accept scalars OR per-request
        arrays of shape (B,) — a formed micro-batch may mix them freely.
        The executor splits the batch into homogeneous (topk, ef) groups,
        runs each through the single-knob pipeline (inputs pad to the
        existing pow2 trace buckets, so no new trace shapes appear) and
        reassembles — bit-identical to issuing each group as its own query.
        ``ef`` entries <= 0 mean "index default".  With mixed ``topk`` the
        outputs are shaped (B, max(topk)); row r carries topk[r] results
        then (+inf, -1) padding.

        Returns (dists, ids); optionally per-query routing stats.

        HNSW partitions additionally run device-resident and trace-stable,
        selected by ``hnsw_mode``:

        * 'stacked' (default) — all partitions packed into one flat padded
          pytree, ONE vmapped ``beam_search_flat`` call per query batch (no
          per-partition Python loop or host<->device sync);
        * 'partition' — per-partition calls against cached device arrays
          padded to shared (n, L) buckets (bounded trace count);
        * 'legacy' — the pre-device-resident path: graph re-uploaded and
          beam_search retraced per routed-subset size (kept as the
          before/after benchmark baseline and a parity oracle).
        """
        if hnsw_mode not in ("stacked", "partition", "legacy"):
            raise ValueError(
                f"hnsw_mode={hnsw_mode!r} — expected 'stacked', 'partition' "
                "or 'legacy'"
            )
        cfg = self.config
        if (
            cfg.quantized == "q8"
            and cfg.engine == "hnsw"
            and hnsw_mode != "stacked"
        ):
            raise ValueError(
                "quantized='q8' with engine='hnsw' serves only "
                "hnsw_mode='stacked' (the flat quantized beam)"
            )
        queries = np.asarray(queries, dtype=np.float32)
        if cfg.metric == "mips":
            if not hasattr(self, "_mips_M2"):
                raise RuntimeError(
                    "metric='mips' index has no stored M^2 — build() it, or "
                    "load() one saved with mips_M2 in its manifest"
                )
            queries = np.concatenate(
                [queries, np.zeros((queries.shape[0], 1), np.float32)], axis=1
            )
        B = queries.shape[0]
        if cfg.engine != "hnsw":
            # ef is an HNSW beam knob — the scan engine ignores it, so
            # normalizing it away BEFORE grouping keeps a formed micro-batch
            # whole instead of fragmenting it into bit-identical groups.
            ef = None
        scalar, groups = knob_groups(topk, ef, B)
        if scalar:
            tk, efv, _ = groups[0]
            return self._query_group(
                queries, tk, efv, return_stats, hnsw_mode
            )
        # mixed knobs: one homogeneous sub-query per group, rows reassembled
        # in place.  Output width is the widest topk; narrower rows carry
        # (+inf, -1) padding past their own topk.
        k_max = max((tk for tk, _, _ in groups), default=0)
        out_d = np.full((B, k_max), np.inf, np.float32)
        out_i = np.full((B, k_max), -1, np.int64)
        group_stats = []
        for tk, efv, rows in groups:
            res = self._query_group(
                queries[rows], tk, efv, return_stats, hnsw_mode
            )
            if return_stats:
                d, i, st = res
                group_stats.append((tk, len(rows), st))
            else:
                d, i = res
            out_d[rows, :tk] = d
            out_i[rows, :tk] = i
        if not return_stats:
            return out_d, out_i
        return out_d, out_i, self._combine_group_stats(group_stats, B)

    def _query_group(self, queries, topk, ef, return_stats, hnsw_mode):
        """One homogeneous (topk, ef) group through the staged executor."""
        cfg = self.config
        pstk = per_shard_topk(topk, cfg.num_shards, cfg.topk_confidence)
        if queries.shape[0] == 0:
            # well-formed empty outputs; routing/merge would otherwise choke
            # on zero-length reductions (segments_visited.max()).
            out_d = np.full((0, topk), np.inf, np.float32)
            out_i = np.full((0, topk), -1, np.int64)
            if return_stats:
                return out_d, out_i, query_stats(
                    pstk, np.zeros((0,), np.int64), choose_merge_path(cfg)
                )
            return out_d, out_i
        out_d, out_i, plan = self._exec.execute(queries, topk, ef, hnsw_mode)
        if return_stats:
            return out_d, out_i, query_stats(
                pstk, plan.segments_visited, plan.merge_path
            )
        return out_d, out_i

    def _combine_group_stats(self, group_stats, B):
        """Fold per-group stats into one batch-level dict (same schema)."""
        if not group_stats:
            # B == 0 with array knobs: same merge-path report as the scalar
            # B == 0 path (the decision is configuration, not batch, state)
            return query_stats(
                0, np.zeros((0,), np.int64),
                choose_merge_path(self.config), knob_groups_count=0,
            )
        stats = dict(group_stats[-1][2])  # trace counters: process-wide
        paths = {st["merge_path"] for _, _, st in group_stats}
        stats["merge_path"] = paths.pop() if len(paths) == 1 else "mixed"
        stats["knob_groups"] = len(group_stats)
        stats["per_shard_topk"] = max(
            st["per_shard_topk"] for _, _, st in group_stats
        )
        stats["mean_segments_visited"] = (
            sum(st["mean_segments_visited"] * n for _, n, st in group_stats)
            / max(B, 1)
        )
        stats["max_segments_visited"] = max(
            st["max_segments_visited"] for _, _, st in group_stats
        )
        return stats

    # -- persistence (atomic, resumable) --------------------------------------

    @staticmethod
    def _partition_path(root, s, g):
        return os.path.join(root, f"shard{s:04d}_seg{g:04d}.npz")

    def _partition_done(self, root, s, g):
        return os.path.exists(self._partition_path(root, s, g))

    def _save_partition(self, root, s, g, payload):
        os.makedirs(root, exist_ok=True)
        path = self._partition_path(root, s, g)
        arrays = {"kind": np.array(payload["kind"])}
        for key, val in payload.items():
            if key == "kind" or val is None:
                continue
            if isinstance(val, list):
                for li, arr in enumerate(val):
                    arrays[f"{key}__{li}"] = arr
                arrays[f"{key}__len"] = np.array(len(val))
            else:
                arrays[key] = np.asarray(val)
        fd, tmp = tempfile.mkstemp(dir=root, suffix=".tmp")
        os.close(fd)
        with open(tmp, "wb") as f:
            np.savez(f, **arrays)
        os.replace(tmp, path)  # atomic publish

    def _load_partition(self, root, s, g):
        with np.load(self._partition_path(root, s, g), allow_pickle=False) as z:
            payload = {}
            lists: dict[str, dict[int, np.ndarray]] = {}
            for key in z.files:
                if "__" in key:
                    base, idx = key.rsplit("__", 1)
                    if idx == "len":
                        payload.setdefault(base, [None] * int(z[key]))
                    else:
                        lists.setdefault(base, {})[int(idx)] = z[key]
                elif key == "kind":
                    payload["kind"] = str(z[key])
                else:
                    payload[key] = z[key]
            for base, items in lists.items():
                payload.setdefault(base, [None] * len(items))
                for idx, arr in items.items():
                    payload[base][idx] = arr
        if payload.get("kind") == "hnsw" and "upper_adj" not in payload:
            # legacy artifact (pre-stacked): rebuild the (L, n, M) stack from
            # the ragged per-level lists it stored.
            from repro.core.hnsw import stack_upper_adj

            payload["upper_adj"] = stack_upper_adj(
                payload.get("level_nodes", []),
                payload.get("level_adj", []),
                payload["vectors"].shape[0],
                self.config.hnsw_config().M,
            )
        return _Partition(payload, self.config)

    def save(self, root: str):
        os.makedirs(root, exist_ok=True)
        for (s, g), part in self.partitions.items():
            if not self._partition_done(root, s, g):
                payload = {"kind": part.kind, "vectors": part.vectors, "keys": part.keys}
                if part.kind == "hnsw":
                    fr = part.frozen
                    payload.update(
                        levels=fr.levels, adj0=fr.adj0, entry=fr.entry,
                        upper_adj=fr.upper_adj,
                    )
                if part.q8 is not None:
                    # quantized payload: int8 codes + per-dim scales +
                    # per-vector norm corrections; the fp32 ``vectors``
                    # above double as the exact re-rank store.
                    payload.update(
                        q8_codes=part.q8.codes,
                        q8_scales=part.q8.scales,
                        q8_norms2=part.q8.norms2,
                    )
                self._save_partition(root, s, g, payload)
        seg = self.partitioner.segmenter
        tree = seg.tree_arrays()
        manifest = {
            # v2 adds the optional q8_* quantized arrays per partition (and
            # the quantized/rerank_* config knobs); v1 artifacts load
            # unchanged — absent fields fall back to fp32 behaviour.
            "format_version": 2,
            "config": dataclasses.asdict(self.config),
            "partitions": sorted([f"{s}/{g}" for s, g in self.partitions]),
            "build_stats": {
                k: v for k, v in self.build_stats.items() if k != "per_partition_seconds"
            },
            # mips needs the corpus max-norm M^2 to convert augmented-L2
            # distances back to inner products at query time.
            "mips_M2": getattr(self, "_mips_M2", None),
        }
        with open(os.path.join(root, "manifest.json"), "w") as f:
            json.dump(manifest, f, indent=2, default=str)
        if tree is not None:
            np.savez(
                os.path.join(root, "segmenter.npz"),
                hyperplanes=tree["hyperplanes"], split=tree["split"],
                lo=tree["lo"], hi=tree["hi"],
            )

    @classmethod
    def load(cls, root: str) -> "LannsIndex":
        with open(os.path.join(root, "manifest.json")) as f:
            manifest = json.load(f)
        version = int(manifest.get("format_version", 1))
        if version > 2:
            raise ValueError(
                f"artifact format_version={version} is newer than this "
                "build understands (max 2)"
            )
        config = LannsConfig(**manifest["config"])
        index = cls(config)
        if manifest.get("mips_M2") is not None:
            index._mips_M2 = float(manifest["mips_M2"])
        seg_path = os.path.join(root, "segmenter.npz")
        if os.path.exists(seg_path):
            with np.load(seg_path) as z:
                seg = index.partitioner.segmenter
                seg.hyperplanes = z["hyperplanes"]
                seg.split = z["split"]
                seg.lo = z["lo"]
                seg.hi = z["hi"]
        index.partitioner._fitted = True
        for pstr in manifest["partitions"]:
            s, g = (int(v) for v in pstr.split("/"))
            index.partitions[(s, g)] = index._load_partition(root, s, g)
        index.build_stats = manifest.get("build_stats", {})
        return index
