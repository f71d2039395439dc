"""Distributed-path tests.  These need >1 device, so each test runs a child
python with XLA_FLAGS=--xla_force_host_platform_device_count set BEFORE jax
imports (the parent test process keeps its single CPU device)."""

import os
import pathlib
import subprocess
import sys
import textwrap

_REPO = pathlib.Path(__file__).resolve().parents[1]


def run_child(code: str, devices: int = 8, timeout: int = 900):
    env = {
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
        "PYTHONPATH": "src",
        "PATH": "/usr/bin:/bin",
        "HOME": os.environ.get("HOME", str(_REPO)),
    }
    env.update({k: v for k, v in os.environ.items()
                if k.startswith(("JAX", "XDG")) and k != "XLA_FLAGS"})
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=_REPO,
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return proc.stdout


def test_distributed_lanns_full_scan_recall():
    """Full-scan distributed serving == brute force up to perShardTopK."""
    out = run_child("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.lanns import LannsConfig
        from repro.core.brute_force import brute_force_topk
        from repro.core.recall import recall_at_k
        from repro.serve.retrieval import build_device_index, make_serve_fn
        from repro.data.synthetic import clustered_vectors

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        # confidence chosen so perShardTopK == k: full scan is then exact
        cfg = LannsConfig(num_shards=4, num_segments=4, segmenter="apd",
                          engine="scan", topk_confidence=1 - 1e-9)
        data = clustered_vectors(4000, 24, n_clusters=64, seed=0)
        qs = clustered_vectors(64, 24, n_clusters=64, seed=1)
        idx = build_device_index(data, cfg)
        serve_fn, sh = make_serve_fn(mesh, cfg, topk=10, mode="full",
                                     batch_per_device=32)
        put = lambda a, name: jax.device_put(a, sh[name])
        d, i, ovf = serve_fn(put(qs, "queries"), put(idx.corpus, "corpus"),
                             put(idx.ids, "ids"), put(idx.norms, "norms"),
                             idx.tree)
        td, ti = brute_force_topk(qs, data, 10)
        r = recall_at_k(np.asarray(i), ti, 10)
        assert r > 0.98, r
        print("RECALL", r)
    """)
    assert "RECALL" in out


def test_distributed_lanns_routed_beats_nothing():
    out = run_child("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.lanns import LannsConfig
        from repro.core.brute_force import brute_force_topk
        from repro.core.recall import recall_at_k
        from repro.serve.retrieval import build_device_index, make_serve_fn
        from repro.data.synthetic import clustered_vectors

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = LannsConfig(num_shards=4, num_segments=4, segmenter="apd",
                          engine="scan", alpha=0.15)
        data = clustered_vectors(4000, 24, n_clusters=64, seed=3)
        qs = clustered_vectors(64, 24, n_clusters=64, seed=4)
        idx = build_device_index(data, cfg)
        serve_fn, sh = make_serve_fn(mesh, cfg, topk=10, mode="routed",
                                     batch_per_device=32, capacity_factor=2.0)
        put = lambda a, name: jax.device_put(a, sh[name])
        d, i, ovf = serve_fn(put(qs, "queries"), put(idx.corpus, "corpus"),
                             put(idx.ids, "ids"), put(idx.norms, "norms"),
                             idx.tree)
        td, ti = brute_force_topk(qs, data, 10)
        r = recall_at_k(np.asarray(i), ti, 10)
        assert r > 0.5, r
        assert int(ovf) == 0
        print("ROUTED_RECALL", r)
    """)
    assert "ROUTED_RECALL" in out


def test_gnn_shard_map_loss_matches_local():
    """The shard_map partitioned GNN loss (and its grads) must equal the
    single-device computation on the same partitions."""
    out = run_child("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.models import dimenet as dn
        from repro.data.synthetic import random_molecule_batch

        cfg = dn.DimeNetConfig(n_blocks=2, d_hidden=16, n_bilinear=2,
                               n_spherical=3, n_radial=3)
        params = dn.init(jax.random.PRNGKey(0), cfg)
        mols = random_molecule_batch(4, n_nodes=10, n_edges=20, seed=0)
        t_in = np.full((4, 64), -1, np.int32); t_out = np.full((4, 64), -1, np.int32)
        for b in range(4):
            ti_, to_ = dn.build_triplets(mols["edge_index"][b], 10)
            m = min(64, len(ti_)); t_in[b, :m] = ti_[:m]; t_out[b, :m] = to_[:m]
        batch = dict(positions=jnp.asarray(mols["positions"]),
                     edge_index=jnp.asarray(mols["edge_index"]),
                     t_in=jnp.asarray(t_in), t_out=jnp.asarray(t_out),
                     z=jnp.asarray(mols["z"]), y=jnp.asarray(mols["y"]))

        def local_loss(p, batch):
            def one(pos, ei, ti, to, z):
                _, g = dn.apply(p, cfg, positions=pos, edge_index=ei,
                                t_in=ti, t_out=to, z=z)
                return g[0]
            pred = jax.vmap(one)(batch["positions"], batch["edge_index"],
                                 batch["t_in"], batch["t_out"], batch["z"])
            return jnp.mean((pred - batch["y"]) ** 2)

        mesh = jax.make_mesh((4,), ("lanes",),
                             axis_types=(jax.sharding.AxisType.Auto,) * 1)
        def lane_loss(p, b):
            bb = jax.tree.map(lambda a: a[0], b)
            _, g = dn.apply(p, cfg, positions=bb["positions"],
                            edge_index=bb["edge_index"], t_in=bb["t_in"],
                            t_out=bb["t_out"], z=bb["z"])
            se = (g[0] - bb["y"]) ** 2
            return jax.lax.psum(se, "lanes") / 4.0
        sm_loss = jax.shard_map(
            lane_loss, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), params),
                      jax.tree.map(lambda _: P("lanes"), batch)),
            out_specs=P(), check_vma=False)

        l0 = float(local_loss(params, batch))
        l1 = float(sm_loss(params, batch))
        assert abs(l0 - l1) < 1e-4 * max(abs(l0), 1), (l0, l1)
        g0 = jax.grad(local_loss)(params, batch)
        g1 = jax.grad(lambda p, b: sm_loss(p, b).sum())(params, batch)
        # psum reassociates f32 sums; compare RELATIVE to grad magnitude
        scale = max(float(jnp.abs(x).max()) for x in jax.tree.leaves(g0))
        errs = jax.tree.map(
            lambda a, b: float(jnp.abs(a - b).max()), g0, g1)
        m = max(jax.tree.leaves(errs)) / max(scale, 1e-9)
        assert m < 1e-3, m
        print("GRAD_MATCH", m)
    """, devices=4)
    assert "GRAD_MATCH" in out


def test_hierarchical_grad_sync_equals_global_mean():
    out = run_child("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.distributed.collectives import hierarchical_grad_sync

        mesh = jax.make_mesh((2, 4), ("pod", "data"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        g = jnp.arange(8 * 33, dtype=jnp.float32).reshape(8, 33)

        def local(gl):
            synced = hierarchical_grad_sync({"w": gl[0]},
                                            pod_axis="pod", local_axis="data")
            return synced["w"][None]

        out = jax.shard_map(local, mesh=mesh,
                        in_specs=(P(("pod", "data"), None),),
                        out_specs=P(("pod", "data"), None),
                        check_vma=False)(g)
        want = g.mean(axis=0)
        for row in np.asarray(out):
            assert np.allclose(row, np.asarray(want), rtol=1e-5), "mismatch"
        print("SYNC_OK")
    """, devices=8)
    assert "SYNC_OK" in out


def test_ring_topk_merge_matches_allgather():
    out = run_child("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.distributed.collectives import ring_topk_merge

        mesh = jax.make_mesh((4,), ("s",),
                             axis_types=(jax.sharding.AxisType.Auto,) * 1)
        rng = np.random.default_rng(0)
        d = jnp.asarray(rng.standard_normal((4, 3, 8)).astype(np.float32))
        ids = jnp.asarray(rng.permutation(4 * 3 * 8).reshape(4, 3, 8).astype(np.int32))

        def local(dl, il):
            md, mi = ring_topk_merge(dl[0], il[0], 5, "s")
            return md[None], mi[None]

        od, oi = jax.shard_map(local, mesh=mesh,
                           in_specs=(P("s"), P("s")),
                           out_specs=(P("s"), P("s")),
                           check_vma=False)(d, ids)
        od, oi = np.asarray(od), np.asarray(oi)
        # reference: global top-5 per row
        flat_d = np.moveaxis(np.asarray(d), 0, -1).reshape(3, 32)
        flat_i = np.moveaxis(np.asarray(ids), 0, -1).reshape(3, 32)
        for r in range(3):
            order = np.argsort(flat_d[r])[:5]
            want = set(flat_i[r][order].tolist())
            for s in range(4):
                assert set(oi[s, r].tolist()) == want
        print("RING_OK")
    """, devices=4)
    assert "RING_OK" in out


def test_debug_mesh_dryrun_smoke():
    """A reduced-config LM cell lowers and compiles on a small debug mesh —
    the CI-scale version of the 512-device dry-run."""
    out = run_child("""
        import jax, jax.numpy as jnp, dataclasses
        from repro.launch.mesh import make_debug_mesh
        from repro.configs import get_arch

        mesh = make_debug_mesh(2, 4)
        arch = get_arch("deepseek-moe-16b")
        # shrink the model but keep the cell machinery
        arch._config = dataclasses.replace(
            arch.model_config(reduced=True), n_layers=3,
            param_dtype="bfloat16", compute_dtype="bfloat16")
        cell = dataclasses.replace(arch.cells["train_4k"], global_batch=8,
                                   seq_len=64)
        arch.num_micro = 2
        spec = arch.build_cell(cell, mesh)
        jitted = jax.jit(spec.fn, in_shardings=spec.in_shardings,
                         out_shardings=spec.out_shardings,
                         donate_argnums=spec.donate_argnums)
        compiled = jitted.lower(*spec.args).compile()
        assert compiled.memory_analysis().temp_size_in_bytes > 0
        print("DEBUG_DRYRUN_OK")
    """, devices=8)
    assert "DEBUG_DRYRUN_OK" in out


def test_distributed_lanns_int8_corpus():
    """SQ8 corpus: 4x smaller, recall within a point of f32 full scan."""
    out = run_child("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.lanns import LannsConfig
        from repro.core.brute_force import brute_force_topk
        from repro.core.recall import recall_at_k
        from repro.serve.retrieval import build_device_index, make_serve_fn
        from repro.data.synthetic import sift_like

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = LannsConfig(num_shards=4, num_segments=4, segmenter="apd",
                          engine="scan", topk_confidence=1 - 1e-9)
        data, qs = sift_like(4000, 24, 64, seed=0)
        idx8 = build_device_index(data, cfg, corpus_dtype="int8")
        assert idx8.corpus.dtype == np.int8 and idx8.scale is not None
        serve_fn, sh = make_serve_fn(mesh, cfg, topk=10, mode="full",
                                     batch_per_device=32)
        put = lambda a, name: jax.device_put(a, sh[name])
        d, i, ovf = serve_fn(put(qs, "queries"), put(idx8.corpus, "corpus"),
                             put(idx8.ids, "ids"), put(idx8.norms, "norms"),
                             idx8.tree, put(idx8.scale, "replicated"))
        td, ti = brute_force_topk(qs, data, 10)
        r = recall_at_k(np.asarray(i), ti, 10)
        assert r > 0.9, r
        print("INT8_RECALL", r)
    """)
    assert "INT8_RECALL" in out


def test_pod_sharded_corpus_two_stage_merge():
    """corpus_axes=('pod','model'): 2x shards, hierarchical gather, exact
    at pstk==k."""
    out = run_child("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.lanns import LannsConfig
        from repro.core.brute_force import brute_force_topk
        from repro.core.recall import recall_at_k
        from repro.serve.retrieval import build_device_index, make_serve_fn
        from repro.data.synthetic import sift_like

        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 3)
        cfg = LannsConfig(num_shards=4, num_segments=2, segmenter="apd",
                          engine="scan", topk_confidence=1 - 1e-9)
        data, qs = sift_like(3000, 16, 32, seed=0)
        idx = build_device_index(data, cfg)
        serve_fn, sh = make_serve_fn(
            mesh, cfg, topk=10, mode="full", batch_per_device=16,
            corpus_axes=("pod", "model"), query_axes=("data",),
        )
        put = lambda a, name: jax.device_put(a, sh[name])
        d, i, ovf = serve_fn(put(qs, "queries"), put(idx.corpus, "corpus"),
                             put(idx.ids, "ids"), put(idx.norms, "norms"),
                             idx.tree)
        td, ti = brute_force_topk(qs, data, 10)
        r = recall_at_k(np.asarray(i), ti, 10)
        assert r > 0.98, r
        print("POD_SHARDED_RECALL", r)
    """)
    assert "POD_SHARDED_RECALL" in out


def test_chip_smoke_sharded_phase_on_four_devices():
    """chip_smoke.py's --four-chips phase, rehearsed at a tiny size on four
    virtual CPU devices: both modes agree with the one-chip scan engine."""
    out = run_child("""
        import chip_smoke
        recs = list(chip_smoke.sharded_phases(4000, 24, n_queries=32))
        assert [r["phase"] for r in recs] == ["sharded_full", "sharded_routed"]
        for r in recs:
            assert r["overflow"] == 0, r
            print(r["phase"], r["recall_at_100"],
                  r["agreement_with_one_chip"], r["one_chip_recall_at_100"])
        print("SHARDED_SMOKE_OK")
    """, devices=4)
    assert "SHARDED_SMOKE_OK" in out
