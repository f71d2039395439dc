"""Collective schedules: hierarchical cross-pod reduction, top-k merge trees.

``hierarchical_grad_sync`` implements the multi-pod gradient path from
DESIGN.md §4: pod-local reduce_scatter -> cross-pod all_reduce on the 1/N
shard -> pod-local all_gather.  Cross-pod links are the scarce resource
(data-center interconnect vs intra-pod ICI); this schedule sends exactly
1/pod_local_size of the gradient bytes across pods vs a naive global
all-reduce, and composes with int8 compression (compression.py) applied only
to the cross-pod hop.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def hierarchical_grad_sync(grads, *, pod_axis: str = "pod", local_axis: str = "data"):
    """Inside shard_map: grads pytree replicated per (pod, data) lane.

    Returns the mean over the full (pod x data) group, computed as
    reduce_scatter(local) -> all_reduce(pod) -> all_gather(local).
    """

    def sync_leaf(g):
        orig_shape = g.shape
        n_local = jax.lax.axis_size(local_axis)
        n_pod = jax.lax.axis_size(pod_axis)
        flat = g.reshape(-1)
        pad = (-flat.shape[0]) % n_local
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), g.dtype)])
        # 1. pod-local reduce_scatter (each lane owns 1/n_local of the sum)
        shard = jax.lax.psum_scatter(
            flat.reshape(n_local, -1), local_axis, scatter_dimension=0, tiled=False
        )
        # 2. cross-pod all_reduce on the shard only
        shard = jax.lax.psum(shard, pod_axis)
        # 3. pod-local all_gather to restore the full gradient
        full = jax.lax.all_gather(shard, local_axis, axis=0, tiled=False)
        full = full.reshape(-1)[: g.size].reshape(orig_shape)
        return full / (n_local * n_pod)

    return jax.tree.map(sync_leaf, grads)


def ring_topk_merge(dists, ids, k: int, axis_name: str):
    """Log-depth alternative to all_gather+merge for the LANNS shard merge:
    butterfly exchange via all-to-all pairs is overkill at pstk payloads, but
    for LARGE k the broker all_gather becomes the bottleneck; this merges
    pairwise over a hypercube in log2(S) rounds, each round halving payload
    growth (candidates stay at k instead of S*k).

    dists/ids: (B, k) local candidates; returns merged (B, k) on every lane.
    Requires power-of-two axis size.
    """
    size = jax.lax.axis_size(axis_name)
    rounds = size.bit_length() - 1
    idx = jax.lax.axis_index(axis_name)
    d, i = dists, ids
    for r in range(rounds):
        partner = idx ^ (1 << r)
        # pairwise exchange via ppermute
        perm = [(s, s ^ (1 << r)) for s in range(size)]
        od = jax.lax.ppermute(d, axis_name, perm)
        oi = jax.lax.ppermute(i, axis_name, perm)
        cd = jnp.concatenate([d, od], axis=-1)
        ci = jnp.concatenate([i, oi], axis=-1)
        neg, sel = jax.lax.top_k(-cd, k)
        d = -neg
        i = jnp.take_along_axis(ci, sel, axis=-1)
    return d, i
