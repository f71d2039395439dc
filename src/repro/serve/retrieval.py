"""Distributed LANNS serving on the TPU mesh (paper §7, TPU-native form).

Topology (DESIGN.md §4): the corpus is sharded along the ``model`` mesh axis —
one LANNS *shard* per model-slice — and the query batch is sharded along the
``data`` axis.  The paper's broker is realized as a collective: each shard
computes its (segment-routed, locally merged) perShardTopK candidates and the
shard merge is an ``all_gather`` over ``model`` followed by a local top-k.
perShardTopK (Eq. 5-6) directly multiplies down the all-gather payload.

Segment routing on-device is MoE-style capacity dispatch: the virtual-spill
tree router yields a (B, m) segment mask; each segment takes up to ``capacity``
queries (gathered, padded), scans its own contiguous row-block with the fused
distance+top-k kernel, and results scatter back per query.  A query spilled to
s segments appears in s dispatch slots and its copies merge in the combine
step — exactly the paper's "merge within the shard" level.

Two scan modes:
  * routed  — capacity-dispatched per-segment scan (the LANNS win: each query
              touches ~(1+2a)^depth/m of the shard).
  * full    — every query scans the whole shard (brute-force baseline and the
              ground-truth path of §5.4).

Multi-pod: with mesh (pod, data, model), the default treats pods as index
replicas (queries sharded over pod x data; zero cross-pod collectives); set
``corpus_axes=("pod", "model")`` to instead shard the corpus over 2*16 shards
and merge with a two-stage hierarchical gather.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common.utils import round_up
from repro.core.lanns import LannsConfig
from repro.core.merge import per_shard_topk
from repro.core.sharding import TwoLevelPartitioner


# ---------------------------------------------------------------------------
# On-device tree router (the jit twin of TreeSegmenter._route)
# ---------------------------------------------------------------------------


def route_queries_tree(tree: dict, q: jnp.ndarray, spill: bool = True) -> jnp.ndarray:
    """(B, d) queries -> (B, m) bool segment mask, fully vectorized.

    tree: dict of stacked heap-order arrays {hyperplanes (m-1, d), split, lo,
    hi} and static int ``depth``.  spill=True routes into the [lo, hi] band
    both ways (virtual spill / Figure 3); spill=False is the median split
    (used for point insertion parity tests).
    """
    depth = int(tree["depth"])
    H = tree["hyperplanes"]
    # full f32 projections, so routing agrees with the host segmenter
    proj = jnp.matmul(q, H.T, precision=jax.lax.Precision.HIGHEST)
    B = q.shape[0]
    mask = jnp.ones((B, 1), dtype=bool)
    for lvl in range(depth):
        nodes = jnp.arange(2**lvl) + (2**lvl - 1)
        p = proj[:, nodes]  # (B, 2^lvl)
        if spill:
            gl = p <= tree["hi"][nodes][None, :]
            gr = p >= tree["lo"][nodes][None, :]
        else:
            gl = p < tree["split"][nodes][None, :]
            gr = ~gl
        mask = jnp.stack([mask & gl, mask & gr], axis=-1).reshape(B, 2 ** (lvl + 1))
    return mask


# ---------------------------------------------------------------------------
# Device-resident index
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DeviceIndex:
    """Stacked per-(shard, segment) corpus blocks, ready for the mesh.

    corpus  (S, m, N_seg, d) f32/bf16/int8 — padded row blocks (zeros invalid)
    ids     (S, m, N_seg)    i32 — global keys, -1 padding
    norms   (S, m, N_seg)    f32 — BUILD-TIME row norms (serving never
                                    re-derives them; §Perf v8)
    scale   (d,) f32 | None      — int8 per-dimension dequant scale (SQ8)
    tree    dict | None          — shared segmenter arrays (replicated)
    """

    corpus: np.ndarray
    ids: np.ndarray
    norms: np.ndarray
    tree: Optional[dict]
    config: LannsConfig
    scale: Optional[np.ndarray] = None

    @property
    def num_shards(self):
        return self.corpus.shape[0]

    @property
    def num_segments(self):
        return self.corpus.shape[1]


def build_device_index(
    data: np.ndarray,
    config: LannsConfig,
    keys: Optional[np.ndarray] = None,
    *,
    pad_multiple: int = 8,
    corpus_dtype: str = "float32",  # 'float32' | 'bfloat16' | 'int8'
) -> DeviceIndex:
    """Two-level partition the corpus and pack it into stacked device blocks.

    Physical spill duplicates rows into both children (paper Table 7's
    ~10-30% memory overhead shows up directly in N_seg).

    corpus_dtype='int8' applies symmetric per-dimension scalar quantization
    (FAISS SQ8 equivalent): 4x HBM saving over f32; norms are computed from
    the ORIGINAL f32 rows so the quantization error only perturbs the cross
    term of the distance.
    """
    data = np.asarray(data, dtype=np.float32)
    n = data.shape[0]
    if keys is None:
        keys = np.arange(n, dtype=np.int64)
    part = TwoLevelPartitioner(config.num_shards, config.segmenter_config())
    part.fit(data)
    assignment = part.assign(data, keys)
    S, m = config.num_shards, config.num_segments
    sizes = assignment.partition_sizes()
    n_seg = round_up(max(int(sizes.max()), 1), pad_multiple)
    scale = None
    if corpus_dtype == "int8":
        scale = (np.abs(data).max(axis=0) / 127.0).astype(np.float32)
        scale = np.maximum(scale, 1e-12)
        store = np.zeros((S, m, n_seg, data.shape[1]), dtype=np.int8)
    else:
        store = np.zeros(
            (S, m, n_seg, data.shape[1]),
            dtype=jnp.dtype(corpus_dtype).type if corpus_dtype != "float32"
            else np.float32,
        )
    ids = np.full((S, m, n_seg), -1, dtype=np.int32)
    norms = np.zeros((S, m, n_seg), dtype=np.float32)
    for s in range(S):
        for g in range(m):
            rows = assignment.rows[s][g]
            block = data[rows]
            if corpus_dtype == "int8":
                store[s, g, : len(rows)] = np.clip(
                    np.round(block / scale[None, :]), -127, 127
                ).astype(np.int8)
            else:
                store[s, g, : len(rows)] = block.astype(store.dtype)
            ids[s, g, : len(rows)] = keys[rows]
            norms[s, g, : len(rows)] = np.einsum("nd,nd->n", block, block)
    seg = part.segmenter
    tree = seg.tree_arrays()
    if tree is not None:
        tree = {
            "hyperplanes": tree["hyperplanes"],
            "split": tree["split"],
            "lo": tree["lo"],
            "hi": tree["hi"],
            "depth": tree["depth"],
        }
    return DeviceIndex(
        corpus=store, ids=ids, norms=norms, tree=tree, config=config,
        scale=scale,
    )


# ---------------------------------------------------------------------------
# Local (per-shard) search — runs inside shard_map
# ---------------------------------------------------------------------------


def _segment_scan_topk(q_seg, x_seg, ids_seg, xn_seg, k, metric,
                       block_n=2048, scale=None):
    """Per-segment blocked scan.  q_seg (m, C, d); x_seg (m, N, d);
    ids_seg (m, N); xn_seg (m, N) BUILD-TIME row norms
    -> (m, C, k) dists/ids (global keys).

    The matmul runs in the corpus dtype (bf16 corpus => bf16 MXU matmul with
    f32 accumulation; int8 corpus dequantizes per block against ``scale``);
    only the running top-k merge stays f32.
    """

    def one(qg, xg, ig, xng):
        N, dim = xg.shape
        bn = min(block_n, N)
        nb = -(-N // bn)
        xp = jnp.pad(xg, ((0, nb * bn - N), (0, 0)))
        ip = jnp.pad(ig, (0, nb * bn - N), constant_values=-1)
        xnp_ = jnp.pad(xng, (0, nb * bn - N))
        compute_dtype = jnp.bfloat16 if xg.dtype == jnp.int8 else xg.dtype
        # an f32 corpus is scanned at full f32 precision (a TPU's default
        # is one bf16 pass); bf16/int8 corpora keep the native MXU pass
        precision = (
            jax.lax.Precision.HIGHEST if compute_dtype == jnp.float32
            else None
        )
        qc = qg.astype(compute_dtype)
        q_norm = jnp.sum(
            qc.astype(jnp.float32) * qc.astype(jnp.float32), -1, keepdims=True
        )

        def step(carry, blk):
            run_d, run_i = carry
            # dynamic-slice the corpus per block: scanning over a stacked
            # (nb, bn, d) xs materialized a full padded copy of the corpus.
            xb = jax.lax.dynamic_slice(xp, (blk * bn, 0), (bn, dim))
            ib = jax.lax.dynamic_slice(ip, (blk * bn,), (bn,))
            xn = jax.lax.dynamic_slice(xnp_, (blk * bn,), (bn,))
            if scale is not None:  # SQ8: dequant fuses into the matmul read
                xb = xb.astype(compute_dtype) * scale.astype(compute_dtype)
            elif xb.dtype != compute_dtype:
                xb = xb.astype(compute_dtype)
            qx = jax.lax.dot_general(
                qc, xb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=precision,
            )  # (C, bn) f32 accum from native-dtype reads
            s = (q_norm - 2.0 * qx + xn[None, :]) if metric == "l2" else -qx
            s = jnp.where((ib >= 0)[None, :], s, jnp.inf)
            # two-stage merge: block-local top-k FIRST (C, k), then a (C, 2k)
            # merge — concatenating the raw (C, bn) scores each block cost
            # ~10x the corpus bytes in merge traffic.  (approx_max_k was
            # tried here — on TPU it lowers to the single-pass PartialReduce
            # — but the CPU lowering falls back to a full sort, so the
            # measured estimate regressed; revisit on hardware. §Perf v7.)
            neg_b, idx_b = jax.lax.top_k(-s, min(k, bn))
            blk_i = ib[idx_b]
            blk_d = -neg_b
            if blk_d.shape[1] < k:
                pad = k - blk_d.shape[1]
                blk_d = jnp.pad(blk_d, ((0, 0), (0, pad)), constant_values=jnp.inf)
                blk_i = jnp.pad(blk_i, ((0, 0), (0, pad)), constant_values=-1)
            cat_d = jnp.concatenate([run_d, blk_d], 1)  # (C, 2k)
            cat_i = jnp.concatenate([run_i, blk_i], 1)
            neg, idx = jax.lax.top_k(-cat_d, k)
            return (-neg, jnp.take_along_axis(cat_i, idx, 1)), None

        C = qg.shape[0]
        init = (
            jnp.full((C, k), jnp.inf, jnp.float32),
            jnp.full((C, k), -1, jnp.int32),
        )
        (d, gi), _ = jax.lax.scan(step, init, jnp.arange(nb))
        return d, gi

    return jax.vmap(one)(q_seg, x_seg, ids_seg, xn_seg)


def _local_shard_search_routed(
    q, corpus, ids, norms, tree, *, k_local, metric, capacity, depth,
    block_n=2048, scale=None,
):
    """Segment-routed search of ONE shard.  q (B, d); corpus (m, N, d)."""
    B = q.shape[0]
    m = corpus.shape[0]
    mask = route_queries_tree(dict(tree, depth=depth), q, spill=True)  # (B, m)
    # capacity dispatch: first `capacity` routed queries per segment.
    pos = jnp.cumsum(mask.astype(jnp.int32), axis=0) - 1  # (B, m) slot per seg
    keep = mask & (pos < capacity)
    overflow = jnp.sum(mask & ~keep)
    # gather query indices per segment: sort (B,) priorities per segment col
    prio = jnp.where(keep, jnp.arange(B, dtype=jnp.int32)[:, None], B)
    order = jnp.argsort(prio, axis=0)  # (B, m) — routed queries first
    sel = order[:capacity].T  # (m, C) query indices (B = invalid)
    valid_slot = jnp.take_along_axis(prio, order, axis=0)[:capacity].T < B  # (m, C)
    q_seg = q[jnp.clip(sel, 0, B - 1)]  # (m, C, d)
    d_seg, i_seg = _segment_scan_topk(
        q_seg, corpus, ids, norms, k_local, metric, block_n=block_n,
        scale=scale,
    )
    d_seg = jnp.where(valid_slot[..., None], d_seg, jnp.inf)
    i_seg = jnp.where(valid_slot[..., None], i_seg, -1)
    # combine: scatter back to (B, m, k_local) then merge the spilled copies.
    buf_d = jnp.full((B, m, k_local), jnp.inf, dtype=d_seg.dtype)
    buf_i = jnp.full((B, m, k_local), -1, dtype=i_seg.dtype)
    seg_idx = jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32)[:, None], sel.shape)
    # invalid slots index out of range (B) and are dropped by the scatter.
    flat_q = jnp.where(valid_slot, sel, B).reshape(-1)
    flat_g = seg_idx.reshape(-1)
    buf_d = buf_d.at[flat_q, flat_g].set(d_seg.reshape(-1, k_local), mode="drop")
    buf_i = buf_i.at[flat_q, flat_g].set(i_seg.reshape(-1, k_local), mode="drop")
    # level-1 merge (inside the shard): across the <=m segment copies
    neg, idx = jax.lax.top_k(-buf_d.reshape(B, -1), k_local)
    out_i = jnp.take_along_axis(buf_i.reshape(B, -1), idx, axis=1)
    return -neg, out_i, overflow


def _local_shard_search_full(q, corpus, ids, norms, *, k_local, metric,
                             block_n=8192, scale=None):
    """Brute scan of the whole shard (ground truth / RS segmenter).

    Reuses the masked blocked scan (padding rows are ZERO vectors whose
    distance ||q||^2 can beat real neighbors — they must be masked BEFORE
    the top-k, which _segment_scan_topk does via its id mask)."""
    m, N, d = corpus.shape
    flat = corpus.reshape(1, m * N, d)
    flat_ids = ids.reshape(1, m * N)
    flat_norms = norms.reshape(1, m * N)
    dd, gi = _segment_scan_topk(
        q[None], flat, flat_ids, flat_norms, k_local, metric,
        block_n=block_n, scale=scale,
    )
    return dd[0], gi[0], jnp.int32(0)


# ---------------------------------------------------------------------------
# The distributed serve step
# ---------------------------------------------------------------------------


def make_serve_fn(
    mesh: Mesh,
    config: LannsConfig,
    *,
    topk: int,
    mode: str = "routed",  # 'routed' | 'full'
    capacity_factor: float = 1.5,
    batch_per_device: int = 64,
    use_per_shard_topk: bool = True,
    corpus_axes: tuple = ("model",),
    query_axes: tuple = ("data",),
    depth: int = 0,
    block_n: int = 2048,
):
    """Build the distributed serve step for a given mesh.

    Returns (serve_fn, shardings).  serve_fn signature:
      (queries (B_global, d), corpus (S, m, N, d), ids (S, m, N),
       norms (S, m, N), tree, scale=None) ->
      (dists (B_global, topk), ids (B_global, topk), overflow count)
    ``shardings`` maps each argument ("queries", "corpus", "ids", "norms",
    "replicated" for tree leaves and scale) and "out" to its
    ``NamedSharding``; place host arrays with ``jax.device_put(a,
    shardings[name])`` so every device receives only its own shard.  It
    also carries the derived "per_shard_topk" and "capacity".
    """
    num_shards = 1
    for a in corpus_axes:
        num_shards *= mesh.shape[a]
    if num_shards != config.num_shards:
        raise ValueError(
            f"config.num_shards={config.num_shards} must equal mesh corpus "
            f"axes product {num_shards}"
        )
    pstk = per_shard_topk(topk, num_shards, config.topk_confidence) if (
        use_per_shard_topk
    ) else topk
    m = config.num_segments
    if depth <= 0:
        depth = int(np.log2(m))
    # expected routed queries/segment: B * (1+2a)^depth / m, plus slack.
    spill_mult = (1.0 + 2.0 * config.alpha) ** depth
    capacity = int(np.ceil(batch_per_device * spill_mult / m * capacity_factor))
    capacity = max(8, min(capacity, batch_per_device))
    metric = "ip" if config.metric in ("ip", "cos") else "l2"

    has_scale = False
    q_spec = P(query_axes, None)
    corpus_spec = P(corpus_axes, None, None, None)
    ids_spec = P(corpus_axes, None, None)  # ids and build-time norms
    out_spec = P(query_axes, None)

    def local_step(q, corpus, ids, norms, *extra):
        # inside shard_map: q (B_loc, d); corpus (1, m, N, d)
        corpus = corpus[0]
        ids_l = ids[0]
        norms_l = norms[0]
        scale = extra[-1] if has_scale else None
        tree_leaves = extra[:-1] if has_scale else extra
        if mode == "routed" and tree_leaves:
            tree = {
                "hyperplanes": tree_leaves[0],
                "split": tree_leaves[1],
                "lo": tree_leaves[2],
                "hi": tree_leaves[3],
            }
            d_l, i_l, ovf = _local_shard_search_routed(
                q, corpus, ids_l, norms_l, tree,
                k_local=pstk, metric=metric, capacity=capacity, depth=depth,
                block_n=block_n, scale=scale,
            )
        else:
            d_l, i_l, ovf = _local_shard_search_full(
                q, corpus, ids_l, norms_l, k_local=pstk, metric=metric,
                scale=scale,
            )
        # ---- level-2 merge: the broker as a collective --------------------
        # all_gather over the corpus axes; payload per query = pstk pairs
        # per shard, which is what Eq. (5)-(6) trims (vs topk without it).
        d_g, i_g = d_l, i_l
        for ax in reversed(corpus_axes):  # innermost axis gathered first
            d_g = jax.lax.all_gather(d_g, ax)
            i_g = jax.lax.all_gather(i_g, ax)
        d_g = d_g.reshape(num_shards, q.shape[0], pstk)
        i_g = i_g.reshape(num_shards, q.shape[0], pstk)
        cand_d = jnp.moveaxis(d_g, 0, 1).reshape(q.shape[0], num_shards * pstk)
        cand_i = jnp.moveaxis(i_g, 0, 1).reshape(q.shape[0], num_shards * pstk)
        neg, idx = jax.lax.top_k(-cand_d, topk)
        out_i = jnp.take_along_axis(cand_i, idx, axis=1)
        ovf = jax.lax.psum(ovf, corpus_axes + query_axes)  # global scalar
        return -neg, out_i, ovf

    def serve_fn(queries, corpus, ids, norms, tree, scale=None):
        nonlocal has_scale
        has_scale = scale is not None
        if mode == "routed" and tree is not None:
            leaves = (tree["hyperplanes"], tree["split"], tree["lo"], tree["hi"])
        else:
            leaves = ()
        if has_scale:
            leaves = leaves + (scale,)
        fn = jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=(q_spec, corpus_spec, ids_spec, ids_spec)
            + tuple(P() for _ in leaves),
            out_specs=(out_spec, out_spec, P()),
            check_vma=False,
        )
        return fn(queries, corpus, ids, norms, *leaves)

    shardings = {
        "queries": NamedSharding(mesh, q_spec),
        "corpus": NamedSharding(mesh, corpus_spec),
        "ids": NamedSharding(mesh, ids_spec),
        "norms": NamedSharding(mesh, ids_spec),
        "replicated": NamedSharding(mesh, P()),  # tree leaves, scale
        "out": NamedSharding(mesh, out_spec),
        "per_shard_topk": pstk,
        "capacity": capacity,
    }
    return serve_fn, shardings
