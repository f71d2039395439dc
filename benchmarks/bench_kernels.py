"""Kernel micro-bench: fused distance+top-k vs unfused oracle, plus the
jitted merge_topk dedup forms (two-lexsort vs retired scatter-min).

On this CPU container wall-clock comes from the XLA:CPU jnp path (the Pallas
kernel itself is validated in interpret mode — a Python loop, not timed).
What IS meaningful here: the memory-traffic model (the fused kernel's reason
to exist) — we report bytes-moved per call for fused vs unfused to quantify
the HBM saving the kernel buys on TPU."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_call
from repro.core.merge import merge_topk, merge_topk_scatter
from repro.kernels import ref


def run_merge():
    """ROADMAP item: the two-lexsort jnp merge_topk vs the old vmapped
    scatter-min, on the (B*S, routes*pstk) shapes the executor produces."""
    rng = np.random.default_rng(0)
    for (R, C, k) in [(1024, 64, 16), (1024, 512, 100), (4096, 128, 32)]:
        d = jnp.asarray(rng.standard_normal((R, C)).astype(np.float32))
        i = jnp.asarray(rng.integers(0, C // 2, (R, C)).astype(np.int32))
        f_lex = jax.jit(lambda d, i, k=k: merge_topk(d, i, k))
        f_sca = jax.jit(lambda d, i, k=k: merge_topk_scatter(d, i, k))
        f_lex(d, i)[0].block_until_ready()
        f_sca(d, i)[0].block_until_ready()
        t_lex, _ = time_call(lambda: f_lex(d, i)[0].block_until_ready(),
                             repeats=5)
        t_sca, _ = time_call(lambda: f_sca(d, i)[0].block_until_ready(),
                             repeats=5)
        emit(
            f"kernel_merge_topk.R{R}.C{C}.k{k}",
            1e6 * t_lex,
            f"scatter_us={1e6 * t_sca:.0f};"
            f"speedup={t_sca / t_lex:.2f}x",
        )


def run():
    rng = np.random.default_rng(0)
    for (B, N, D, k) in [(64, 100_000, 50, 100), (16, 100_000, 128, 100),
                         (256, 20_000, 64, 10)]:
        q = jnp.asarray(rng.standard_normal((B, D)).astype(np.float32))
        x = jnp.asarray(rng.standard_normal((N, D)).astype(np.float32))

        f_ref = jax.jit(lambda q, x, k=k: ref.distance_topk_ref(q, x, k, "l2"))
        f_blk = jax.jit(
            lambda q, x, k=k: ref.distance_topk_blocked(q, x, k, "l2")
        )
        f_ref(q, x)[0].block_until_ready()
        f_blk(q, x)[0].block_until_ready()
        t_ref, _ = time_call(lambda: f_ref(q, x)[0].block_until_ready(), repeats=5)
        t_blk, _ = time_call(lambda: f_blk(q, x)[0].block_until_ready(), repeats=5)

        # memory model (f32): unfused writes+rereads the (B, N) score matrix;
        # fused streams it through VMEM.
        bytes_unfused = 4 * (N * D + B * D + 2 * B * N + B * k * 2)
        bytes_fused = 4 * (N * D + B * D + B * k * 2)
        emit(
            f"kernel_dist_topk.B{B}.N{N}.D{D}.k{k}",
            1e6 * t_blk,
            f"unfused_us={1e6 * t_ref:.0f};hbm_bytes_fused={bytes_fused:.3e};"
            f"hbm_bytes_unfused={bytes_unfused:.3e};"
            f"traffic_saving={bytes_unfused / bytes_fused:.2f}x",
        )
    run_merge()


if __name__ == "__main__":
    from repro.common.utils import enable_compile_cache

    enable_compile_cache()
    run()
