"""Arch families: everything needed to smoke-test and dry-run one cell.

An ``Arch`` bundles:
  * the exact published model config (+ a reduced smoke twin),
  * its shape cells (name -> Cell),
  * ``build_cell(cell, mesh, ctx)`` -> ``LoweredSpec``: the step function,
    abstract inputs (ShapeDtypeStruct — never allocated), and in/out
    shardings for ``jit(...).lower(...)``.

Dtype policy: dry-run cells use bf16 params/compute with f32 optimizer
moments (production mixed precision); smoke tests run f32 on CPU.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.sharding_rules import ShardingCtx
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import (
    dimenet_loss_fn,
    lm_loss_fn,
    make_train_step,
    recsys_loss_fn,
)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    kind: str  # 'train' | 'prefill' | 'decode' | 'forward' | 'retrieval'
    global_batch: int = 1
    seq_len: int = 0
    extra: tuple = ()  # extra (key, value) pairs

    def get(self, key, default=None):
        return dict(self.extra).get(key, default)


@dataclasses.dataclass
class LoweredSpec:
    """What launch/dryrun.py feeds to jit(...).lower()."""

    fn: Callable
    args: tuple  # ShapeDtypeStructs (abstract) or concrete arrays
    in_shardings: Any
    out_shardings: Any
    note: str = ""
    model_flops_per_step: float = 0.0  # 6*N*D (dense) / 6*N_active*D (MoE)
    donate_argnums: tuple = ()  # in-place buffers (params/opt/KV cache)
    aux_info: dict = dataclasses.field(default_factory=dict)


def _sds(tree):
    """pytree of arrays/structs -> ShapeDtypeStructs."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree
    )


def _named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda s: isinstance(s, P),
    )


def make_ctx(mesh: Optional[Mesh], *, pod_dp: bool = True) -> ShardingCtx:
    ctx = ShardingCtx(mesh=mesh)
    if mesh is not None and "pod" in mesh.shape and pod_dp:
        rules = dict(ctx.rules)
        rules["batch"] = ("pod", "data")
        ctx = dataclasses.replace(ctx, rules=rules)
    return ctx


class Arch:
    arch_id: str = ""
    family: str = ""
    cells: dict = {}

    # -- interface -----------------------------------------------------------
    def model_config(self, reduced: bool = False):
        raise NotImplementedError

    def build_cell(self, cell: Cell, mesh: Mesh) -> LoweredSpec:
        raise NotImplementedError

    def smoke(self, seed: int = 0) -> dict:
        """Reduced-config forward+train step on CPU; returns metrics."""
        raise NotImplementedError

    def cell_names(self):
        return list(self.cells)


# ===========================================================================
# LM family
# ===========================================================================

LM_CELLS = {
    "train_4k": Cell("train_4k", "train", global_batch=256, seq_len=4096),
    "prefill_32k": Cell("prefill_32k", "prefill", global_batch=32, seq_len=32_768),
    "decode_32k": Cell("decode_32k", "decode", global_batch=128, seq_len=32_768),
    "long_500k": Cell("long_500k", "decode", global_batch=1, seq_len=524_288),
}


class LMArch(Arch):
    family = "lm"

    def __init__(self, arch_id: str, config, *, num_micro: int = 16,
                 tp: bool = True, remat_group: int = 0,
                 smoke_overrides: Optional[dict] = None):
        """tp=False: pure FSDP/DP — the 'model' axis joins the batch/FSDP
        axes instead of tensor-parallelism.  The right layout for small
        models (smollm: 15 heads don't divide any TP width; TP would
        replicate attention scores on every chip)."""
        self.arch_id = arch_id
        self._config = config
        self.cells = dict(LM_CELLS)
        self.num_micro = num_micro
        self.tp = tp
        self.remat_group = remat_group
        self.smoke_overrides = smoke_overrides or {}

    def model_config(self, reduced: bool = False):
        if not reduced:
            return self._config
        cfg = self._config
        moe = cfg.moe
        if moe is not None:
            # generous capacity so smoke decode-parity is exact (capacity
            # drops are the one legitimate prefill/train divergence)
            moe = dataclasses.replace(
                moe, num_experts=8, top_k=2, d_ff_expert=64, n_shared=1,
                capacity_factor=8.0,
            )
        return dataclasses.replace(
            cfg,
            n_layers=2 + (moe.first_k_dense if moe else 0),
            d_model=64,
            n_heads=4,
            n_kv_heads=min(cfg.n_kv_heads, 4) if cfg.n_kv_heads < cfg.n_heads else 4,
            head_dim=16,
            d_ff=128,
            vocab=512,
            moe=moe,
            mla_kv_lora_rank=32,
            mla_qk_nope_head_dim=16,
            mla_qk_rope_head_dim=8,
            mla_v_head_dim=16,
            q_chunk=0,
            remat=False,
            param_dtype="float32",
            compute_dtype="float32",
            **self.smoke_overrides,
        )

    # -- dry-run construction --------------------------------------------------
    def _abstract_params(self, cfg, ctx):
        from repro.models import transformer as tf

        params = jax.eval_shape(lambda k: tf.init(k, cfg), jax.random.PRNGKey(0))
        specs = tf.param_specs(params, cfg, ctx)
        return params, specs

    def _dryrun_model_cfg(self, cell: Cell):
        # chunked (flash-style) attention everywhere except decode: bounds
        # the live f32 score buffer to q_chunk x kv_chunk even when the head
        # count doesn't divide the TP width (smollm: 15 heads on 16-way TP
        # replicates scores — 7.5 GiB/layer unchunked).
        cfg = dataclasses.replace(
            self._config,
            param_dtype="bfloat16",
            compute_dtype="bfloat16",
            remat=cell.kind == "train",
            remat_group=self.remat_group if cell.kind == "train" else 0,
            q_chunk=0 if cell.kind == "decode" else 1024,
            kv_chunk=2048,
        )
        return cfg

    def build_cell(self, cell: Cell, mesh: Mesh) -> LoweredSpec:
        from repro.models import transformer as tf
        from repro.serve.engine import make_decode_fn, make_prefill_fn

        ctx = make_ctx(mesh)
        if not self.tp:
            # nothing model-sharded; for training the model axis joins the
            # batch/FSDP axes (serving keeps batch on 'data' so the KV cache
            # can use 'model' for its sequence dim).
            rules = dict(ctx.rules)
            rules["model"] = ()
            rules["vocab"] = ()
            rules["expert"] = ()
            if cell.kind == "train":
                if "pod" in mesh.shape:
                    # 512 lanes would exceed global_batch=256: batch over
                    # (pod, data) = 32 lanes, weights FSDP over 'model'
                    rules["batch"] = ("pod", "data")
                    rules["fsdp"] = ("model",)
                else:
                    rules["batch"] = ("data", "model")
            ctx = dataclasses.replace(ctx, rules=rules)
        cfg = self._dryrun_model_cfg(cell)
        params, pspecs = self._abstract_params(cfg, ctx)
        B = cell.global_batch
        S = cell.seq_len
        tokens_per_step = B * S
        n_active = cfg.num_active_params()
        batch_spec = ctx.spec("batch")

        if cell.kind == "train":
            mf = 6.0 * n_active * tokens_per_step
            opt_cfg = AdamWConfig(lr=3e-4, total_steps=10_000)
            loss = lm_loss_fn(cfg, ctx)
            num_micro = self.num_micro
            if not self.tp and "pod" in mesh.shape:
                # 32 batch lanes instead of 256: microbatch to keep the
                # unsharded-vocab logits buffer at 1 seq/lane
                num_micro = max(num_micro, 8)
            step = make_train_step(loss, opt_cfg, num_micro=num_micro)
            opt_specs = {
                "step": P(),
                "m": pspecs,
                "v": pspecs,
            }
            opt_abs = {
                "step": jax.ShapeDtypeStruct((), jnp.int32),
                "m": jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params
                ),
                "v": jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params
                ),
            }
            batch_abs = {
                "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
                "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
            }
            batch_specs = {
                "tokens": P(*batch_spec, None),
                "labels": P(*batch_spec, None),
            }
            in_sh = (
                _named(mesh, pspecs),
                _named(mesh, opt_specs),
                _named(mesh, batch_specs),
            )
            out_sh = (
                _named(mesh, pspecs),
                _named(mesh, opt_specs),
                None,
            )
            return LoweredSpec(
                fn=step,
                args=(params, opt_abs, batch_abs),
                in_shardings=in_sh,
                out_shardings=out_sh,
                model_flops_per_step=mf,
                note=f"microbatch={num_micro}, remat, fsdp+tp",
                donate_argnums=(0, 1),
            )

        # serving cells share bf16 cache; sharding of the cache seq dim is
        # the per-cell decision (DESIGN.md §4)
        if cfg.attention == "mla":
            cache_abs = {
                "latent": jax.ShapeDtypeStruct(
                    (cfg.n_layers, B, S, cfg.mla_kv_lora_rank), jnp.bfloat16
                ),
                "k_rope": jax.ShapeDtypeStruct(
                    (cfg.n_layers, B, S, cfg.mla_qk_rope_head_dim), jnp.bfloat16
                ),
            }
        else:
            kvh = cfg.n_kv_heads
            cache_abs = {
                "k": jax.ShapeDtypeStruct(
                    (cfg.n_layers, B, S, kvh, cfg.head_dim), jnp.bfloat16
                ),
                "v": jax.ShapeDtypeStruct(
                    (cfg.n_layers, B, S, kvh, cfg.head_dim), jnp.bfloat16
                ),
            }
        batch_axes_mesh = tuple(
            a for a in ctx.rules["batch"] if a in mesh.shape
        )
        if cell.name == "long_500k":
            # whole mesh serves one stream: KV seq sharded over data x model
            seq_axes = ("data", "model")
            cache_batch = ()
        elif cell.kind == "decode":
            seq_axes = ("model",)
            cache_batch = batch_axes_mesh  # must match token batch axes —
            # a (pod,data)-sharded batch writing a (data,)-sharded cache made
            # GSPMD gather k/v across pods (+75 GiB temp on moe prefill)
        else:  # prefill: batch-sharded cache, seq sharded on model
            seq_axes = ("model",)
            cache_batch = batch_axes_mesh
        cache_specs = jax.tree.map(
            lambda s: P(None, cache_batch if cache_batch else None, seq_axes)
            if s.ndim >= 3
            else P(),
            cache_abs,
        )
        # serving params: TP only (no fsdp gather per token step? keep fsdp
        # for memory; decode weights gathered per layer like prefill)
        serve_pspecs = pspecs
        n_dev = int(np.prod(list(mesh.shape.values())))
        n_dev_cache = 256 if "pod" in mesh.shape else n_dev  # pods replicate
        cache_bytes_device = sum(
            int(np.prod(c.shape)) * c.dtype.itemsize for c in jax.tree.leaves(cache_abs)
        ) // n_dev_cache

        if cell.kind == "prefill":
            fn = make_prefill_fn(cfg, ctx)
            tokens_abs = jax.ShapeDtypeStruct((B, S), jnp.int32)
            in_sh = (
                _named(mesh, serve_pspecs),
                NamedSharding(mesh, P(*batch_spec, None)),
                _named(mesh, cache_specs),
            )
            out_sh = (
                NamedSharding(mesh, P(*batch_spec, None)),
                _named(mesh, cache_specs),
            )
            mf = 2.0 * n_active * tokens_per_step
            return LoweredSpec(
                fn=fn,
                args=(params, tokens_abs, cache_abs),
                in_shardings=in_sh,
                out_shardings=out_sh,
                model_flops_per_step=mf,
                note="chunked attention, bf16 cache",
                donate_argnums=(2,),
                aux_info={"cache_bytes_device": cache_bytes_device},
            )

        # decode
        fn = make_decode_fn(cfg, ctx)
        tok_abs = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        off_abs = jax.ShapeDtypeStruct((), jnp.int32)
        tok_spec = P(*batch_spec, None) if B > 1 else P(None, None)
        in_sh = (
            _named(mesh, serve_pspecs),
            NamedSharding(mesh, tok_spec),
            _named(mesh, cache_specs),
            NamedSharding(mesh, P()),
        )
        out_sh = (
            NamedSharding(mesh, tok_spec),
            _named(mesh, cache_specs),
        )
        mf = 2.0 * n_active * B  # one token per slot
        return LoweredSpec(
            fn=fn,
            args=(params, tok_abs, cache_abs, off_abs),
            in_shardings=in_sh,
            out_shardings=out_sh,
            model_flops_per_step=mf,
            note=f"kv seq axes={seq_axes}",
            donate_argnums=(2,),
            aux_info={"cache_bytes_device": cache_bytes_device},
        )

    # -- smoke ------------------------------------------------------------------
    def smoke(self, seed: int = 0) -> dict:
        from repro.data.synthetic import token_batch
        from repro.models import transformer as tf
        from repro.train.optimizer import init_state

        cfg = self.model_config(reduced=True)
        key = jax.random.PRNGKey(seed)
        params = tf.init(key, cfg)
        toks, labels = token_batch(4, 16, cfg.vocab, seed=seed)
        batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}
        logits, _, _ = tf.apply(params, cfg, batch["tokens"])
        assert logits.shape == (4, 16, cfg.vocab)
        assert bool(jnp.isfinite(logits).all())
        opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=10)
        step = jax.jit(make_train_step(lm_loss_fn(cfg), opt_cfg, num_micro=2))
        st = init_state(params)
        p2, st, m1 = step(params, st, batch)
        _, _, m2 = step(p2, st, batch)
        assert np.isfinite(float(m1["loss"])) and float(m2["loss"]) < float(m1["loss"]) * 1.5
        # decode parity with cache
        cache = tf.make_cache(cfg, 2, 20, dtype=jnp.float32)
        lg_p, cache, _ = tf.apply(params, cfg, batch["tokens"][:2, :8], cache=cache, cache_offset=0)
        lg_d, cache, _ = tf.apply(params, cfg, batch["tokens"][:2, 8:9], cache=cache, cache_offset=8)
        lg_full, _, _ = tf.apply(params, cfg, batch["tokens"][:2, :9])
        err = float(jnp.abs(lg_d[:, 0] - lg_full[:, 8]).max())
        assert err < 1e-3, err
        return {"loss0": float(m1["loss"]), "loss1": float(m2["loss"]),
                "decode_err": err}


# ===========================================================================
# GNN family (DimeNet)
# ===========================================================================

GNN_CELLS = {
    # full-batch small graph (cora-scale): fits replicated, single step.
    "full_graph_sm": Cell(
        "full_graph_sm", "train",
        extra=(("n_nodes", 2708), ("n_edges", 10556), ("d_feat", 1433),
               ("triplet_cap", 8)),
    ),
    # sampled-training on a reddit-scale graph: the real neighbor sampler
    # (data/sampler.py) produces per-lane padded subgraphs.
    "minibatch_lg": Cell(
        "minibatch_lg", "train",
        extra=(("n_nodes", 232_965), ("n_edges", 114_615_892),
               ("batch_nodes", 1024), ("fanout", (15, 10)),
               ("n_max", 16_384), ("e_max", 16_384), ("t_max", 32_768)),
    ),
    # full-batch LARGE graph: halo-partitioned data parallelism (DistDGL
    # style) — each chip owns one locality partition (nodes + halo, local
    # edges + capped triplets); grads psum.  A naive edge-sharded layout
    # would force a 15.8 GB message all-gather per block (see DESIGN.md §4).
    "ogb_products": Cell(
        "ogb_products", "train",
        extra=(("n_nodes", 2_449_029), ("n_edges", 61_859_140), ("d_feat", 100),
               ("triplet_cap", 4), ("n_loc", 16_384), ("e_loc", 262_144)),
    ),
    "molecule": Cell(
        "molecule", "train", global_batch=128,
        extra=(("n_nodes", 30), ("n_edges", 64), ("t_max", 256)),
    ),
}


class GNNArch(Arch):
    family = "gnn"

    def __init__(self, arch_id: str, config):
        self.arch_id = arch_id
        self._config = config
        self.cells = dict(GNN_CELLS)

    def model_config(self, reduced: bool = False):
        if not reduced:
            return self._config
        return dataclasses.replace(
            self._config, n_blocks=2, d_hidden=32, n_bilinear=4,
            n_spherical=4, n_radial=4,
        )

    def _cfg_for_cell(self, cell: Cell):
        d_feat = cell.get("d_feat", 0)
        return dataclasses.replace(
            self._config,
            d_node_feat=d_feat or 0,
            param_dtype="bfloat16",
            compute_dtype="bfloat16",
        )

    def build_cell(self, cell: Cell, mesh: Mesh) -> LoweredSpec:
        from repro.models import dimenet as dn

        ctx = make_ctx(mesh)
        cfg = self._cfg_for_cell(cell)
        params = jax.eval_shape(lambda k: dn.init(k, cfg), jax.random.PRNGKey(0))
        pspecs = jax.tree.map(lambda _: P(), params)  # small model: replicate
        opt_cfg = AdamWConfig(lr=1e-3, total_steps=10_000)
        loss = dimenet_loss_fn(cfg, ctx)

        def shard_mapped_loss(batch_specs_tree, lane_axes):
            """Partition-parallel loss via shard_map: each device runs DimeNet
            on its own halo partition; only the scalar loss (and, via AD, the
            parameter grads) cross devices.  GSPMD propagation through the
            vmapped form replicated the (T, h) triplet tensors instead
            (measured 242 GiB/device of collectives on ogb_products)."""
            from repro.distributed.sharding_rules import NULL_CTX

            def lane_loss(p, batch):
                b = jax.tree.map(lambda a: a[0], batch)  # local lane
                node_pred, _ = dn.apply(
                    p, cfg, positions=b["positions"],
                    edge_index=b["edge_index"], t_in=b["t_in"],
                    t_out=b["t_out"], z=b.get("z"),
                    node_feat=b.get("features"),
                    node_mask=b.get("node_mask"), ctx=NULL_CTX,
                )
                mask = (
                    b["node_mask"].astype(jnp.float32)
                    if "node_mask" in b
                    else jnp.ones(node_pred.shape[0], jnp.float32)
                )
                se = (node_pred[:, 0] - b["y"]) ** 2 * mask
                s = jax.lax.psum(jnp.sum(se), lane_axes)
                c = jax.lax.psum(jnp.sum(mask), lane_axes)
                return s / jnp.maximum(c, 1.0)

            smapped = jax.shard_map(
                lane_loss,
                mesh=mesh,
                in_specs=(jax.tree.map(lambda _: P(), params), batch_specs_tree),
                out_specs=P(),
                check_vma=False,
            )
            return lambda p, batch: (smapped(p, batch), jnp.float32(0.0))

        f32, i32 = jnp.float32, jnp.int32
        lane_axes = tuple(a for a in ("pod", "data", "model") if a in mesh.shape)
        n_lanes = int(np.prod([mesh.shape[a] for a in lane_axes]))
        bf16 = jnp.bfloat16

        def lane_specs(abs_tree, axes):
            return jax.tree.map(
                lambda s: P(axes, *((None,) * (s.ndim - 1))), abs_tree
            )

        if cell.name == "molecule":
            B = cell.global_batch
            nn_, ne = cell.get("n_nodes"), cell.get("n_edges")
            t_max = cell.get("t_max")
            batch_abs = {
                "positions": jax.ShapeDtypeStruct((B, nn_, 3), f32),
                "edge_index": jax.ShapeDtypeStruct((B, 2, ne), i32),
                "t_in": jax.ShapeDtypeStruct((B, t_max), i32),
                "t_out": jax.ShapeDtypeStruct((B, t_max), i32),
                "z": jax.ShapeDtypeStruct((B, nn_), i32),
                "y": jax.ShapeDtypeStruct((B,), f32),
            }
            batch_specs = lane_specs(batch_abs, ctx.spec("batch")[0])
        elif cell.name == "minibatch_lg":
            # one sampled subgraph per batch lane (data axes); 1024 seeds
            # split over the lanes.
            lanes = int(np.prod([mesh.shape[a] for a in ("pod", "data")
                                 if a in mesh.shape]))
            n_max, e_max, t_max = (
                cell.get("n_max"), cell.get("e_max"), cell.get("t_max")
            )
            batch_abs = {
                "positions": jax.ShapeDtypeStruct((lanes, n_max, 3), f32),
                "edge_index": jax.ShapeDtypeStruct((lanes, 2, e_max), i32),
                "t_in": jax.ShapeDtypeStruct((lanes, t_max), i32),
                "t_out": jax.ShapeDtypeStruct((lanes, t_max), i32),
                "z": jax.ShapeDtypeStruct((lanes, n_max), i32),
                "y": jax.ShapeDtypeStruct((lanes,), f32),
            }
            batch_specs = lane_specs(batch_abs, ctx.spec("batch")[0])
        elif cell.name == "ogb_products":
            # halo partitions: one per chip (over ALL mesh axes)
            n_loc, e_loc = cell.get("n_loc"), cell.get("e_loc")
            t_loc = e_loc * cell.get("triplet_cap")
            d_feat = cell.get("d_feat")
            batch_abs = {
                "positions": jax.ShapeDtypeStruct((n_lanes, n_loc, 3), f32),
                "edge_index": jax.ShapeDtypeStruct((n_lanes, 2, e_loc), i32),
                "t_in": jax.ShapeDtypeStruct((n_lanes, t_loc), i32),
                "t_out": jax.ShapeDtypeStruct((n_lanes, t_loc), i32),
                "features": jax.ShapeDtypeStruct((n_lanes, n_loc, d_feat), bf16),
                "node_mask": jax.ShapeDtypeStruct((n_lanes, n_loc), jnp.bool_),
                "y": jax.ShapeDtypeStruct((n_lanes, n_loc), f32),
            }
            batch_specs = lane_specs(batch_abs, lane_axes)
            loss = shard_mapped_loss(batch_specs, lane_axes)
        else:  # full_graph_sm: replicated single graph
            n, E = cell.get("n_nodes"), cell.get("n_edges")
            cap = cell.get("triplet_cap")
            T = E * cap
            batch_abs = {
                "positions": jax.ShapeDtypeStruct((n, 3), f32),
                "edge_index": jax.ShapeDtypeStruct((2, E), i32),
                "t_in": jax.ShapeDtypeStruct((T,), i32),
                "t_out": jax.ShapeDtypeStruct((T,), i32),
                "features": jax.ShapeDtypeStruct((n, cell.get("d_feat")), f32),
                "y": jax.ShapeDtypeStruct((n,), f32),
                "node_mask": jax.ShapeDtypeStruct((n,), jnp.bool_),
            }
            batch_specs = jax.tree.map(lambda s: P(), batch_abs)

        step = make_train_step(loss, opt_cfg, num_micro=1)
        opt_abs = {
            "step": jax.ShapeDtypeStruct((), jnp.int32),
            "m": jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params
            ),
            "v": jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params
            ),
        }
        opt_specs = {"step": P(), "m": pspecs, "v": pspecs}
        in_sh = (
            _named(mesh, pspecs),
            _named(mesh, opt_specs),
            _named(mesh, batch_specs),
        )
        out_sh = (_named(mesh, pspecs), _named(mesh, opt_specs), None)
        # FLOPs proxy: 6 * params-touched-per-edge * edges processed
        if cell.name == "molecule":
            n_edges_step = cell.global_batch * cell.get("n_edges", 0)
        elif cell.name == "minibatch_lg":
            n_edges_step = batch_abs["edge_index"].shape[0] * cell.get("e_max")
        elif cell.name == "ogb_products":
            n_edges_step = n_lanes * cell.get("e_loc")
        else:
            n_edges_step = cell.get("n_edges", 1)
        per_edge_params = cfg.num_params() / max(cfg.n_blocks, 1)
        mf = 6.0 * per_edge_params * max(n_edges_step, 1)
        return LoweredSpec(
            fn=step,
            args=(params, opt_abs, batch_abs),
            in_shardings=in_sh,
            out_shardings=out_sh,
            model_flops_per_step=mf,
            note=f"layout={cell.name}; triplet cap {cell.get('triplet_cap')}",
            donate_argnums=(0, 1),
        )

    def smoke(self, seed: int = 0) -> dict:
        from repro.data.synthetic import random_molecule_batch
        from repro.models import dimenet as dn
        from repro.train.optimizer import init_state

        cfg = self.model_config(reduced=True)
        key = jax.random.PRNGKey(seed)
        params = dn.init(key, cfg)
        mols = random_molecule_batch(4, n_nodes=12, n_edges=24, seed=seed)
        t_in = np.full((4, 64), -1, np.int32)
        t_out = np.full((4, 64), -1, np.int32)
        for b in range(4):
            ti, to = dn.build_triplets(mols["edge_index"][b], 12)
            m = min(64, len(ti))
            t_in[b, :m], t_out[b, :m] = ti[:m], to[:m]
        batch = {
            "positions": jnp.asarray(mols["positions"]),
            "edge_index": jnp.asarray(mols["edge_index"]),
            "t_in": jnp.asarray(t_in),
            "t_out": jnp.asarray(t_out),
            "z": jnp.asarray(mols["z"]),
            "y": jnp.asarray(mols["y"]),
        }
        opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)
        step = jax.jit(make_train_step(dimenet_loss_fn(cfg), opt_cfg))
        st = init_state(params)
        p, st, m1 = step(params, st, batch)
        losses = [float(m1["loss"])]
        for _ in range(5):
            p, st, mm = step(p, st, batch)
            losses.append(float(mm["loss"]))
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0]
        return {"loss0": losses[0], "loss_last": losses[-1]}


# ===========================================================================
# RecSys family
# ===========================================================================

RECSYS_CELLS = {
    "train_batch": Cell("train_batch", "train", global_batch=65_536),
    "serve_p99": Cell("serve_p99", "forward", global_batch=512),
    "serve_bulk": Cell("serve_bulk", "forward", global_batch=262_144),
    "retrieval_cand": Cell(
        "retrieval_cand", "retrieval", global_batch=1,
        extra=(("n_candidates", 1_000_000),),
    ),
}


class RecsysArch(Arch):
    family = "recsys"

    def __init__(self, arch_id: str, config, *, embed_dim_retrieval: int = 0):
        self.arch_id = arch_id
        self._config = config
        self.cells = dict(RECSYS_CELLS)
        self.embed_dim_retrieval = embed_dim_retrieval

    def model_config(self, reduced: bool = False):
        from repro.models import recsys as rs

        cfg = self._config
        if not reduced:
            return cfg
        small = {"param_dtype": "float32", "compute_dtype": "float32"}
        if isinstance(cfg, rs.AutoIntConfig):
            return dataclasses.replace(cfg, vocab_sizes=(64,) * cfg.n_sparse, **small)
        if isinstance(cfg, rs.DINConfig):
            return dataclasses.replace(
                cfg, n_items=256, context_vocab=64, seq_len=16, **small
            )
        if isinstance(cfg, rs.SASRecConfig):
            return dataclasses.replace(cfg, n_items=256, seq_len=16, **small)
        if isinstance(cfg, rs.XDeepFMConfig):
            return dataclasses.replace(
                cfg, vocab_sizes=(64,) * cfg.n_sparse,
                cin_layers=(16, 16), mlp=(32, 32), **small
            )
        raise TypeError(type(cfg))

    # ---- batch spec per arch -------------------------------------------------
    def _batch_abs(self, cfg, B: int, for_loss: bool):
        from repro.models import recsys as rs

        f32, i32 = jnp.float32, jnp.int32
        if isinstance(cfg, (rs.AutoIntConfig, rs.XDeepFMConfig)):
            b = {"sparse_ids": jax.ShapeDtypeStruct((B, cfg.n_sparse), i32)}
        elif isinstance(cfg, rs.DINConfig):
            b = {
                "history": jax.ShapeDtypeStruct((B, cfg.seq_len), i32),
                "hist_len": jax.ShapeDtypeStruct((B,), i32),
                "target_item": jax.ShapeDtypeStruct((B,), i32),
                "context_ids": jax.ShapeDtypeStruct((B, cfg.n_context), i32),
            }
        elif isinstance(cfg, rs.SASRecConfig):
            b = {"item_seq": jax.ShapeDtypeStruct((B, cfg.seq_len), i32)}
            if for_loss:
                b["next_items"] = jax.ShapeDtypeStruct((B, cfg.seq_len), i32)
                b["neg_items"] = jax.ShapeDtypeStruct((B, cfg.seq_len), i32)
        else:
            raise TypeError(type(cfg))
        if for_loss and not isinstance(cfg, rs.SASRecConfig):
            b["label"] = jax.ShapeDtypeStruct((B,), f32)
        return b

    def _param_specs(self, params):
        """Embedding tables row-sharded over 'model' (LANNS level-1 applied
        to tables); small dense layers replicated."""

        def spec_for(path, leaf):
            names = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
            if "table" in names[-1] or (len(names) >= 2 and "table" in names[-2]):
                if leaf.ndim == 2 and leaf.shape[0] >= 4096:
                    return P("model", None)
            if names[-1] == "offsets":
                return P()
            return P(*([None] * leaf.ndim))

        return jax.tree_util.tree_map_with_path(spec_for, params)

    def _init_abstract(self, cfg):
        from repro.models import recsys as rs

        if isinstance(cfg, rs.AutoIntConfig):
            init = rs.autoint_init
        elif isinstance(cfg, rs.DINConfig):
            init = rs.din_init
        elif isinstance(cfg, rs.SASRecConfig):
            init = rs.sasrec_init
        else:
            init = rs.xdeepfm_init
        return jax.eval_shape(lambda k: init(k, cfg), jax.random.PRNGKey(0))

    def _forward_fn(self, cfg, ctx):
        from repro.models import recsys as rs

        if isinstance(cfg, rs.AutoIntConfig):
            return lambda p, b: rs.autoint_apply(p, cfg, b["sparse_ids"], ctx)
        if isinstance(cfg, rs.DINConfig):
            return lambda p, b: rs.din_apply(
                p, cfg, history=b["history"], hist_len=b["hist_len"],
                target_item=b["target_item"], context_ids=b["context_ids"], ctx=ctx,
            )
        if isinstance(cfg, rs.SASRecConfig):
            return lambda p, b: rs.sasrec_encode(p, cfg, b["item_seq"], ctx)[:, -1]
        return lambda p, b: rs.xdeepfm_apply(p, cfg, b["sparse_ids"], ctx)

    def build_cell(self, cell: Cell, mesh: Mesh) -> LoweredSpec:
        from repro.models import recsys as rs

        ctx = make_ctx(mesh)
        cfg = dataclasses.replace(
            self._config, param_dtype="bfloat16", compute_dtype="bfloat16"
        )
        params = self._init_abstract(cfg)
        pspecs = self._param_specs(params)
        batch_spec = ctx.spec("batch")
        n_params = cfg.num_params()

        if cell.kind == "train":
            B = cell.global_batch
            arch = cfg.name
            opt_cfg = AdamWConfig(lr=1e-3, total_steps=100_000)
            loss = recsys_loss_fn(arch, cfg, ctx)
            step = make_train_step(loss, opt_cfg, num_micro=1)
            batch_abs = self._batch_abs(cfg, B, for_loss=True)
            batch_specs = jax.tree.map(
                lambda s: P(batch_spec[0] if batch_spec else None,
                            *((None,) * (s.ndim - 1))),
                batch_abs,
            )
            opt_abs = {
                "step": jax.ShapeDtypeStruct((), jnp.int32),
                "m": jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params
                ),
                "v": jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params
                ),
            }
            opt_specs = {"step": P(), "m": pspecs, "v": pspecs}
            return LoweredSpec(
                fn=step,
                args=(params, opt_abs, batch_abs),
                in_shardings=(
                    _named(mesh, pspecs), _named(mesh, opt_specs),
                    _named(mesh, batch_specs),
                ),
                out_shardings=(
                    _named(mesh, pspecs), _named(mesh, opt_specs), None
                ),
                model_flops_per_step=6.0 * B * self._active_params_per_example(cfg),
                note="tables row-sharded on model",
                donate_argnums=(0, 1),
            )

        if cell.kind == "forward":
            B = cell.global_batch
            fwd = self._forward_fn(cfg, ctx)
            batch_abs = self._batch_abs(cfg, B, for_loss=False)
            batch_specs = jax.tree.map(
                lambda s: P(batch_spec[0] if batch_spec else None,
                            *((None,) * (s.ndim - 1))),
                batch_abs,
            )
            return LoweredSpec(
                fn=fwd,
                args=(params, batch_abs),
                in_shardings=(_named(mesh, pspecs), _named(mesh, batch_specs)),
                out_shardings=None,
                model_flops_per_step=2.0 * B * self._active_params_per_example(cfg),
                note="online inference",
            )

        # retrieval_cand: user-tower forward (batch=1) + LANNS shard scan
        # over 1M candidate embeddings sharded across every chip + top-k
        # merge — the paper's PYMK retrieval served by this framework
        # (DESIGN.md §7).  A learned projection maps the tower output to the
        # candidate embedding space (two-tower serving layout).
        n_cand = cell.get("n_candidates")
        n_cand_pad = -(-n_cand // 512) * 512  # shard evenly over all chips
        d_emb = self.embed_dim_retrieval or 64
        fwd = self._forward_fn(cfg, ctx)
        batch_abs = self._batch_abs(cfg, cell.global_batch, for_loss=False)
        # user tower output dim: probe via eval_shape
        u_shape = jax.eval_shape(fwd, params, batch_abs)
        ud = int(np.prod(u_shape.shape[1:])) if u_shape.ndim > 1 else 1
        cand_abs = jax.ShapeDtypeStruct((n_cand_pad, d_emb), jnp.bfloat16)
        proj_abs = jax.ShapeDtypeStruct((max(ud, 1), d_emb), jnp.bfloat16)
        topk = 100
        lane_axes_r = tuple(
            a for a in ("pod", "data", "model") if a in mesh.shape
        )

        def retrieval_step(params, batch, candidates, user_proj):
            u = fwd(params, batch)
            u = u.reshape(1, -1).astype(jnp.bfloat16)
            u = (u @ user_proj).astype(candidates.dtype)
            scores = (u @ candidates.T).astype(jnp.float32)  # (1, n_cand_pad)
            pad_mask = jnp.arange(scores.shape[-1]) < n_cand
            scores = jnp.where(pad_mask[None, :], scores, -jnp.inf)
            top, idx = jax.lax.top_k(scores, topk)
            return top, idx

        batch_specs = jax.tree.map(lambda s: P(*([None] * s.ndim)), batch_abs)
        return LoweredSpec(
            fn=retrieval_step,
            args=(params, batch_abs, cand_abs, proj_abs),
            in_shardings=(
                _named(mesh, pspecs),
                _named(mesh, batch_specs),
                NamedSharding(mesh, P(lane_axes_r, None)),
                NamedSharding(mesh, P()),
            ),
            out_shardings=None,
            model_flops_per_step=2.0 * n_cand * d_emb,
            note="candidate corpus sharded over all chips (LANNS shard scan)",
        )

    def _active_params_per_example(self, cfg):
        """Params touched per example (embedding rows looked up + MLPs)."""
        from repro.models import recsys as rs

        if isinstance(cfg, rs.AutoIntConfig):
            dh = cfg.d_attn * cfg.n_heads
            mlp = cfg.n_sparse * cfg.embed_dim * dh * 4 * cfg.n_attn_layers
            return cfg.n_sparse * cfg.embed_dim + mlp + cfg.n_sparse * dh
        if isinstance(cfg, rs.DINConfig):
            d = cfg.embed_dim
            att = 4 * d * cfg.attn_mlp[0] + cfg.attn_mlp[0] * cfg.attn_mlp[1]
            mlp_in = 2 * d + cfg.n_context * d
            mlp = mlp_in * cfg.mlp[0] + cfg.mlp[0] * cfg.mlp[1]
            return (cfg.seq_len + 1 + cfg.n_context) * d + cfg.seq_len * att + mlp
        if isinstance(cfg, rs.SASRecConfig):
            d = cfg.embed_dim
            per = 6 * d * d
            return cfg.seq_len * d + cfg.n_blocks * cfg.seq_len * per / cfg.seq_len
        if isinstance(cfg, rs.XDeepFMConfig):
            d = cfg.embed_dim
            cin = 0
            hk_prev = cfg.n_sparse
            for hk in cfg.cin_layers:
                cin += hk_prev * cfg.n_sparse * hk * d
                hk_prev = hk
            dims = (cfg.n_sparse * d,) + cfg.mlp + (1,)
            mlp = sum(dims[i] * dims[i + 1] for i in range(len(dims) - 1))
            return cfg.n_sparse * d + cin + mlp
        raise TypeError(type(cfg))

    def smoke(self, seed: int = 0) -> dict:
        from repro.data.synthetic import criteo_like_batch
        from repro.models import recsys as rs
        from repro.train.optimizer import init_state

        cfg = self.model_config(reduced=True)
        key = jax.random.PRNGKey(seed)
        params_init = self._init_abstract  # noqa
        if isinstance(cfg, rs.AutoIntConfig):
            params = rs.autoint_init(key, cfg)
            data = criteo_like_batch(32, n_sparse=cfg.n_sparse,
                                     vocab_sizes=list(cfg.vocab_sizes), seed=seed)
            batch = {"sparse_ids": jnp.asarray(data["sparse_ids"]),
                     "label": jnp.asarray(data["label"])}
        elif isinstance(cfg, rs.XDeepFMConfig):
            params = rs.xdeepfm_init(key, cfg)
            data = criteo_like_batch(32, n_sparse=cfg.n_sparse,
                                     vocab_sizes=list(cfg.vocab_sizes), seed=seed)
            batch = {"sparse_ids": jnp.asarray(data["sparse_ids"]),
                     "label": jnp.asarray(data["label"])}
        elif isinstance(cfg, rs.DINConfig):
            params = rs.din_init(key, cfg)
            data = criteo_like_batch(
                32, n_sparse=cfg.n_context, vocab_sizes=[cfg.context_vocab] * cfg.n_context,
                hist_len=cfg.seq_len, n_items=cfg.n_items, seed=seed,
            )
            batch = {
                "history": jnp.asarray(data["history"]),
                "hist_len": jnp.asarray(data["hist_len"]),
                "target_item": jnp.asarray(data["target_item"]),
                "context_ids": jnp.asarray(data["sparse_ids"]),
                "label": jnp.asarray(data["label"]),
            }
        else:
            params = rs.sasrec_init(key, cfg)
            rng = np.random.default_rng(seed)
            seq = rng.integers(0, cfg.n_items, (32, cfg.seq_len + 1))
            batch = {
                "item_seq": jnp.asarray(seq[:, :-1], jnp.int32),
                "next_items": jnp.asarray(seq[:, 1:], jnp.int32),
            }
        opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=30)
        step = jax.jit(
            make_train_step(recsys_loss_fn(cfg.name, cfg), opt_cfg)
        )
        st = init_state(params)
        p, st, m1 = step(params, st, batch)
        losses = [float(m1["loss"])]
        for _ in range(8):
            p, st, mm = step(p, st, batch)
            losses.append(float(mm["loss"]))
        assert all(np.isfinite(l) for l in losses), losses
        assert losses[-1] < losses[0], losses
        return {"loss0": losses[0], "loss_last": losses[-1]}
