"""Shared utilities: hashing, padding, timing, pytree accounting.

Everything here is dependency-light (numpy + jax only) and deterministic.
"""

from __future__ import annotations

import os
import time
from typing import Iterable

import jax
import numpy as np


# ---------------------------------------------------------------------------
# Hashing — splitmix64 is the canonical cheap 64-bit mixer; we use it for the
# LANNS level-1 hash sharding ("when a point is inserted, it is hashed to ONE
# particular shard using the key of the data point", §4.1).  It must be (a)
# deterministic across hosts, (b) well mixed so shards are balanced, which the
# paper relies on ("the data distribution in our shards is uniform", §5.1).
# ---------------------------------------------------------------------------

_SM64_C1 = np.uint64(0xBF58476D1CE4E5B9)
_SM64_C2 = np.uint64(0x94D049BB133111EB)
_SM64_GAMMA = np.uint64(0x9E3779B97F4A7C15)


def splitmix64(x: np.ndarray) -> np.ndarray:
    """Vectorized splitmix64 finalizer over uint64 arrays."""
    x = np.asarray(x, dtype=np.uint64)
    with np.errstate(over="ignore"):
        z = x + _SM64_GAMMA
        z = (z ^ (z >> np.uint64(30))) * _SM64_C1
        z = (z ^ (z >> np.uint64(27))) * _SM64_C2
        z = z ^ (z >> np.uint64(31))
    return z


def stable_hash_u64(keys: np.ndarray, salt: int = 0) -> np.ndarray:
    """Deterministic 64-bit hash of integer keys (any integer dtype)."""
    k = np.asarray(keys).astype(np.uint64, copy=False)
    return splitmix64(k ^ np.uint64(salt))


# ---------------------------------------------------------------------------
# Shape helpers
# ---------------------------------------------------------------------------


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (and >= 1)."""
    return 1 << max(n - 1, 1).bit_length() if n & (n - 1) else max(n, 1)


def next_pow2_quarter(n: int) -> int:
    """Smallest v >= n on the quarter-pow2 grid {4,5,6,7} * 2^e (plus the
    exact small values 1..4).

    Shape-bucketing compromise: pow2 buckets waste up to 2x padded work,
    exact shapes retrace per size; quarter steps bound padding waste at 25%
    while keeping the trace count logarithmic."""
    n = max(int(n), 1)
    if n <= 4:
        return n
    step = 1 << ((n - 1).bit_length() - 3)
    return -(-n // step) * step


def pad_to(a: np.ndarray, n: int, fill=0) -> np.ndarray:
    """Pad axis 0 of ``a`` up to length ``n`` with ``fill``."""
    if a.shape[0] == n:
        return a
    if a.shape[0] > n:
        raise ValueError(f"cannot pad {a.shape[0]} down to {n}")
    pad_width = [(0, n - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    return np.pad(a, pad_width, constant_values=fill)


def pad_axis_to(a: np.ndarray, axis: int, n: int, fill=0) -> np.ndarray:
    if a.shape[axis] == n:
        return a
    pad_width = [(0, 0)] * a.ndim
    pad_width[axis] = (0, n - a.shape[axis])
    return np.pad(a, pad_width, constant_values=fill)


# ---------------------------------------------------------------------------
# Timing / accounting
# ---------------------------------------------------------------------------


def jit_cache_size(fn) -> int:
    """Compiled-trace count of a jitted function, -1 if unavailable.

    ``_cache_size`` is a private jax API (undocumented); serving stats must
    degrade, not crash, if it goes away.
    """
    try:
        return int(fn._cache_size())
    except Exception:
        return -1


#: default persistent compile cache: a fixed path inside the checkout
_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Entry points (``chip_smoke.py``, the benchmarks) call this once before
    their first compile; the library never does.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no other
    directory is set here; otherwise the cache goes to
    ``<checkout>/.jax_cache``.  The compile-time threshold drops to zero so
    the ~1 s scan-kernel compiles are cached too.
    """
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


class Timer:
    """Context-manager wall timer. ``with Timer() as t: ...; t.seconds``."""

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self.t0
        return False


def tree_count(tree) -> int:
    """Total number of array elements in a pytree."""
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def tree_bytes(tree) -> int:
    return sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(tree)
    )


def batched(it: Iterable, n: int):
    """Yield lists of up to n items."""
    buf = []
    for x in it:
        buf.append(x)
        if len(buf) == n:
            yield buf
            buf = []
    if buf:
        yield buf
