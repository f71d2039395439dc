"""Run LANNS serving end to end on a TPU and check its answers.

    python chip_smoke.py               # one chip: the four phases below
    python chip_smoke.py --four-chips  # four chips: the sharded index only

Phases on one chip, each built through ``LannsIndex.build``, warmed with
``warm_traces`` and answering requests through ``AsyncAnnFrontend``:

* ``scan_fp32`` — 4M x 256d clustered corpus (4 GiB fp32), l2, 1 shard x 16
  APD segments, alpha 0.15, top-100, served by the fused Pallas scan kernel;
* ``scan_q8``   — the same corpus as int8 codes with an exact fp32 re-rank;
* ``hnsw_fp32`` / ``hnsw_q8`` — 200k x 128d, the HNSW engine (fp32 beam and
  quantized beam), built on the host by spawned workers.

``--four-chips`` runs the sharded index (``make_serve_fn``): one LANNS shard
of the one-chip size per chip on a (data=1, model=4) mesh, the broker merge
an ``all_gather``, in ``full`` and ``routed`` mode, compared with an exact
brute force and with the one-chip fp32 scan on the same data.

Every phase checks recall@100 of its answers on 256 queries against an exact
numpy brute force: it must reach the phase's floor and come within 0.01 of
what the phase printed when run on the CPU at the same seed and size.  The
script refuses to run without a TPU.  Progress goes to stdout as one JSON
object per line; the last line is the result,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
printed only when every phase passed.  Any failure exits non-zero.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import tempfile
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.common.utils import enable_compile_cache, jit_cache_size  # noqa: E402
from repro.core.lanns import LannsConfig, LannsIndex  # noqa: E402
from repro.kernels.distance_topk import binned_topk  # noqa: E402
from repro.serve import AsyncAnnFrontend  # noqa: E402

SEED = 0
TOPK = 100
MAX_BATCH = 256
N_REQUESTS = 512  # served per phase; the first N_CHECK are checked
N_CHECK = 256
WAIT_S = 300.0  # per-request wait; a hung batcher fails the phase

SCAN_SIZE = (4_000_000, 256)
HNSW_SIZE = (200_000, 128)

#: recall@100 each phase must reach (exact brute force on N_CHECK queries)
RECALL_FLOOR = {
    "scan_fp32": 0.83,
    "scan_q8": 0.83,
    "hnsw_fp32": 0.77,
    "hnsw_q8": 0.77,
    "sharded_full": 0.95,
    "sharded_routed": 0.80,
}
#: what each phase printed on the CPU (``rehearse()`` with
#: JAX_PLATFORMS=cpu, the same sizes and seed, on a v5e host's CPU); the
#: chip's recall must come within REHEARSAL_TOL of it
CPU_RECALL = {
    "scan_fp32": 0.8461328124999999,
    "scan_q8": 0.8443749999999999,
    "hnsw_fp32": 0.7862499999999999,
    "hnsw_q8": 0.786171875,
}
REHEARSAL_TOL = 0.01


def log(**fields):
    print(json.dumps(fields), flush=True)


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, from JAX's
    monitoring events (a cache hit still reports its retrieval time)."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.cache_hits}


# -- data ---------------------------------------------------------------------


def make_vectors(n, d, n_queries, seed, chunk=1 << 18):
    """Seeded anisotropic Gaussian-mixture corpus plus held-out queries.

    ~300 points per cluster and a 1/i spectrum, as ``sift_like``; the noise
    is drawn on the default device in fixed-size chunks (uniform with unit
    variance) and copied into one host array.
    """
    nc = max(32, n // 300)
    spec = 1.0 / np.arange(1, d + 1, dtype=np.float32)
    spec = jnp.asarray(spec / np.sqrt((spec**2).mean()))
    k_c, k_x, k_q = jax.random.split(jax.random.key(seed), 3)
    centers = jax.random.normal(k_c, (nc, d), jnp.float32) * spec
    centers = centers / jnp.linalg.norm(centers, axis=1, keepdims=True)

    @jax.jit
    def draw(key):
        k_l, k_n = jax.random.split(key)
        lab = jax.random.randint(k_l, (chunk,), 0, nc)
        u = jax.random.uniform(k_n, (chunk, d), jnp.float32)
        return centers[lab] + 0.15 * (u - 0.5) * np.sqrt(12.0) * spec

    def rows(key, m):
        out = np.empty((m, d), np.float32)
        for i, s in enumerate(range(0, m, chunk)):
            e = min(s + chunk, m)
            out[s:e] = np.asarray(draw(jax.random.fold_in(key, i)))[: e - s]
        return out

    return rows(k_x, n), rows(k_q, n_queries)


def exact_topk(data, queries, k, chunk=1 << 18):
    """Exact l2 top-k ids by numpy brute force, chunked over the corpus;
    corpus chunks are scored on a thread pool (numpy releases the GIL)."""
    q = np.asarray(queries, np.float32)

    def chunk_topk(s):
        x = data[s: s + chunk]
        dist = np.einsum("nd,nd->n", x, x)[None, :] - 2.0 * (q @ x.T)
        kk = min(k, dist.shape[1])
        part = np.argpartition(dist, kk - 1, axis=1)[:, :kk]
        return np.take_along_axis(dist, part, axis=1), part + s

    with ThreadPoolExecutor(max_workers=os.cpu_count() or 1) as ex:
        parts = list(ex.map(chunk_topk, range(0, len(data), chunk)))
    cat_d = np.concatenate([p[0] for p in parts], axis=1)
    cat_i = np.concatenate([p[1] for p in parts], axis=1)
    best = np.argpartition(cat_d, k - 1, axis=1)[:, :k]
    return np.take_along_axis(cat_i, best, axis=1)


def recall(pred, truth):
    """Mean fraction of the true top-k ids found in the predicted top-k."""
    k = truth.shape[1]
    return float(np.mean([
        len(set(p[:k].tolist()) & set(t.tolist())) / k
        for p, t in zip(pred, truth)
    ]))


# -- serving ------------------------------------------------------------------


def serve(index, queries):
    """Answer every query as one request through ``AsyncAnnFrontend``.

    Fails on a batcher error, a cancelled request or a wait that times out —
    a crashed batch cancels its requests rather than raising in the caller.
    """
    fe = AsyncAnnFrontend(index, topk=TOPK, max_batch=MAX_BATCH,
                          max_wait_ms=5.0)
    fe.start()
    try:
        reqs = [fe.submit(q) for q in queries]
        for r in reqs:
            if not r.wait(timeout=WAIT_S):
                raise RuntimeError(f"request {r.uid} not answered in {WAIT_S}s")
    finally:
        fe.stop(drain=False, timeout=WAIT_S)
    if fe.error is not None:
        raise RuntimeError("the frontend's batcher failed") from fe.error
    cancelled = sum(r.cancelled for r in reqs)
    if cancelled:
        raise RuntimeError(f"{cancelled} requests were cancelled")
    ids = np.stack([r.ids for r in reqs])
    dists = np.stack([r.dists for r in reqs])
    if ids.shape != (len(queries), TOPK) or (ids < 0).any():
        raise RuntimeError(f"malformed answers: shape {ids.shape}")
    if not np.isfinite(dists).all() or (np.diff(dists, axis=1) < 0).any():
        raise RuntimeError("distances are not finite and ascending")
    return ids, fe.stats["batches"]


def check_recall(phase, r):
    """Hold a phase's recall to its floor and, for the one-chip phases, to
    its CPU rehearsal (the sharded phases compare with the one-chip scan
    themselves)."""
    if r < RECALL_FLOOR[phase]:
        raise RuntimeError(
            f"{phase}: recall@{TOPK} {r:.4f} < floor {RECALL_FLOOR[phase]}"
        )
    if phase not in CPU_RECALL:
        return
    want = CPU_RECALL[phase]
    if abs(r - want) > REHEARSAL_TOL:
        raise RuntimeError(
            f"{phase}: recall@{TOPK} {r:.4f} is not within {REHEARSAL_TOL} "
            f"of the CPU rehearsal's {want:.4f}"
        )


def serve_phase(phase, index, queries, truth, *, warm=True):
    """Warm, serve and score one built index; returns the phase record."""
    t0 = time.perf_counter()
    if warm:
        index.warm_traces(MAX_BATCH, TOPK)
    t1 = time.perf_counter()
    ids, batches = serve(index, queries)
    t2 = time.perf_counter()
    r = recall(ids[: len(truth)], truth)
    return {"phase": phase, "recall_at_100": r, "warm_s": t1 - t0,
            "serve_s": t2 - t1, "requests": len(queries), "batches": batches}


def scan_phases(n, d, *, seed=SEED, n_requests=N_REQUESTS, n_check=N_CHECK,
                warm=True):
    """fp32 and q8 scan engine on one corpus; yields one record per phase."""
    t0 = time.perf_counter()
    data, queries = make_vectors(n, d, n_requests, seed)
    truth = exact_topk(data, queries[:n_check], TOPK)
    setup = {"data_s": time.perf_counter() - t0, "n": n, "d": d}
    cfg = LannsConfig(num_shards=1, num_segments=16, segmenter="apd",
                      alpha=0.15, metric="l2", engine="scan", seed=seed)
    for phase, quantized in (("scan_fp32", "none"), ("scan_q8", "q8")):
        t_b = time.perf_counter()
        index = LannsIndex(dataclasses.replace(cfg, quantized=quantized))
        index.build(data)
        build_s = time.perf_counter() - t_b
        kernels_before = jit_cache_size(binned_topk)
        rec = serve_phase(phase, index, queries, truth, warm=warm)
        rec.update(setup, build_s=build_s)
        if phase == "scan_fp32":
            rec["pallas_kernel_traces"] = (
                jit_cache_size(binned_topk) - kernels_before
            )
        del index
        gc.collect()
        yield rec


def hnsw_phases(n, d, *, seed=SEED, n_requests=N_REQUESTS, n_check=N_CHECK,
                workers=None, warm=True):
    """fp32 and q8 HNSW beam on one corpus; the graphs are built once, by
    spawned workers, and the q8 index resumes from the same artifacts."""
    t0 = time.perf_counter()
    data, queries = make_vectors(n, d, n_requests, seed + 1)
    truth = exact_topk(data, queries[:n_check], TOPK)
    setup = {"data_s": time.perf_counter() - t0, "n": n, "d": d}
    if workers is None:
        workers = max(1, min((os.cpu_count() or 2) - 1, 16))
    cfg = LannsConfig(num_shards=1, num_segments=16, segmenter="apd",
                      alpha=0.15, metric="l2", engine="hnsw", seed=seed)
    with tempfile.TemporaryDirectory(prefix="lanns_hnsw_") as art:
        for phase, quantized in (("hnsw_fp32", "none"), ("hnsw_q8", "q8")):
            t_b = time.perf_counter()
            index = LannsIndex(dataclasses.replace(cfg, quantized=quantized))
            index.build(data, workers=workers, resume_dir=art)
            build_s = time.perf_counter() - t_b
            rec = serve_phase(phase, index, queries, truth, warm=warm)
            rec.update(setup, build_s=build_s, build_workers=workers)
            del index
            gc.collect()
            yield rec


def sharded_phases(n_per_shard, d, *, seed=SEED, n_queries=N_CHECK):
    """The four-chip sharded index vs brute force and the one-chip scan."""
    from repro.serve.retrieval import build_device_index, make_serve_fn

    t0 = time.perf_counter()
    data, queries = make_vectors(4 * n_per_shard, d, n_queries, seed)
    truth = exact_topk(data, queries, TOPK)
    setup = {"data_s": time.perf_counter() - t0, "n": len(data), "d": d,
             "num_shards": 4}
    cfg = LannsConfig(num_shards=4, num_segments=16, segmenter="apd",
                      alpha=0.15, metric="l2", engine="scan", seed=seed)
    # the one-chip fp32 scan engine (default device) on the same data and
    # the same partitioning
    t_b = time.perf_counter()
    one = LannsIndex(cfg).build(data)
    _, one_ids = one.query(queries, TOPK)
    setup["one_chip_s"] = time.perf_counter() - t_b
    setup["one_chip_recall_at_100"] = one_recall = recall(one_ids, truth)
    del one
    gc.collect()

    t_b = time.perf_counter()
    didx = build_device_index(data, cfg)
    del data
    gc.collect()
    mesh = jax.make_mesh(
        (1, 4), ("data", "model"), devices=jax.devices()[:4],
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )
    tree = {k: didx.tree[k] for k in ("hyperplanes", "split", "lo", "hi")}
    placed = None
    for mode in ("full", "routed"):
        # capacity = the whole batch: no routed query is ever dropped
        serve_fn, sh = make_serve_fn(
            mesh, cfg, topk=TOPK, mode=mode, batch_per_device=n_queries,
            capacity_factor=float(cfg.num_segments),
        )
        if placed is None:
            # host -> each chip's own shard: the corpus never lands whole
            # on one device
            placed = [
                jax.device_put(queries, sh["queries"]),
                jax.device_put(didx.corpus, sh["corpus"]),
                jax.device_put(didx.ids, sh["ids"]),
                jax.device_put(didx.norms, sh["norms"]),
                jax.device_put(tree, sh["replicated"]),
            ]
            jax.block_until_ready(placed)
            setup["place_s"] = time.perf_counter() - t_b
            didx = None
            gc.collect()
        t_s = time.perf_counter()
        d_out, i_out, ovf = jax.jit(serve_fn)(*placed)
        ids = np.asarray(i_out)
        phase = f"sharded_{mode}"
        r = recall(ids, truth)
        agree = recall(ids, one_ids)  # share of the one-chip answers found
        rec = {"phase": phase, "recall_at_100": r,
               "agreement_with_one_chip": agree, "overflow": int(ovf),
               "first_call_s": time.perf_counter() - t_s,
               "per_shard_topk": sh["per_shard_topk"], **setup}
        if not np.isfinite(np.asarray(d_out)).all() or (ids < 0).any():
            raise RuntimeError(f"{phase}: malformed answers")
        if int(ovf):
            raise RuntimeError(f"{phase}: {int(ovf)} routed queries dropped")
        if mode == "full" and r < one_recall - REHEARSAL_TOL:
            raise RuntimeError(f"{phase}: full scan recalls less than routed")
        if mode == "routed" and (
            abs(r - one_recall) > REHEARSAL_TOL or agree < 0.99
        ):
            raise RuntimeError(
                f"{phase}: recall {r:.4f} / agreement {agree:.4f} vs the "
                f"one-chip scan's {one_recall:.4f}"
            )
        yield rec


def one_chip_runs():
    """The one-chip phases, grouped by the corpus they share."""
    return [("scan", lambda **kw: scan_phases(*SCAN_SIZE, **kw)),
            ("hnsw", lambda **kw: hnsw_phases(*HNSW_SIZE, **kw))]


def rehearse():
    """Print every one-chip phase's record on whatever device JAX uses —
    the CPU rehearsal that ``CPU_RECALL`` records (no warm-up, no checks,
    never a result line):

        JAX_PLATFORMS=cpu python -c "import chip_smoke; chip_smoke.rehearse()"
    """
    dev = jax.devices()[0]
    log(rehearsal=dev.platform, seed=SEED)
    for _, run in one_chip_runs():
        for rec in run(warm=False):
            log(platform=dev.platform, **rec)


# -- main ---------------------------------------------------------------------


def require_tpu():
    """The device JAX uses by default; raises unless it is a TPU."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"chip_smoke.py needs a TPU; JAX's default device is "
            f"{dev.platform!r} ({dev.device_kind})"
        )
    return dev


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded index on four chips")
    args = ap.parse_args(argv)
    try:
        dev = require_tpu()
    except RuntimeError as e:
        print(str(e), file=sys.stderr)
        return 2
    count = 4 if args.four_chips else 1
    if len(jax.devices()) < count:
        print(f"--four-chips needs 4 devices, found {len(jax.devices())}",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    log(device=dev.device_kind, devices=len(jax.devices()),
        compile_cache=cache_dir, jax=jax.__version__)
    if args.four_chips:
        runs = [("sharded", lambda: sharded_phases(*SCAN_SIZE))]
    else:
        log(note=f"HNSW phases at {HNSW_SIZE[0]} x {HNSW_SIZE[1]}: a size "
                 "set by the host's graph build (spawned workers), not by "
                 "the chip's memory; build_s says what it took")
        runs = one_chip_runs()
    failed = []
    t_all = time.perf_counter()
    for name, run in runs:
        try:
            for rec in run():
                stats = dev.memory_stats() or {}
                rec.update(meter.snapshot(),
                           peak_bytes_in_use=stats.get("peak_bytes_in_use"))
                log(**rec)
                try:
                    check_recall(rec["phase"], rec["recall_at_100"])
                    if rec.get("pallas_kernel_traces", 1) <= 0:
                        raise RuntimeError(
                            f"{rec['phase']} did not run the Pallas scan kernel"
                        )
                except RuntimeError as e:  # the next phase still runs
                    failed.append(rec["phase"])
                    log(failed=rec["phase"], error=str(e))
        except Exception as e:  # noqa: BLE001 — report, finish, exit non-zero
            traceback.print_exc()
            failed.append(name)
            log(failed=name, error=repr(e))
    log(total_s=time.perf_counter() - t_all, **meter.snapshot())
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
