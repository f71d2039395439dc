"""Seeded corpus and queries, drawn on the device.

The generator is the one ``chip_smoke.py`` uses (``make_vectors``): an
anisotropic Gaussian mixture with about 300 points a cluster and a 1/i
spectrum, cluster centres on the unit sphere, uniform noise of unit
variance scaled by 0.15.  It is split into fixed-size chunks, each drawn by
one compiled program from ``fold_in(key, chunk)``, so the reference can
draw the corpus again chunk by chunk on the device and never needs a
second copy of it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: rows per chunk hold about this many float32 values (256 MiB)
CHUNK_VALUES = 1 << 26


def base_key(seed: int):
    """A PRNG key from any whole number: ``jax.random.key`` keeps only the
    low 32 bits, so the high bits are folded in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed={seed} must be >= 0")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def chunk_rows(d: int) -> int:
    """Rows per chunk: the largest power of two within ``CHUNK_VALUES``."""
    return 1 << max(10, int(np.log2(CHUNK_VALUES // d)))


@functools.partial(jax.jit, static_argnames=("rows",))
def _draw(key, centers, spec, rows):
    k_l, k_n = jax.random.split(key)
    lab = jax.random.randint(k_l, (rows,), 0, centers.shape[0])
    u = jax.random.uniform(k_n, (rows, centers.shape[1]), jnp.float32)
    return centers[lab] + 0.15 * (u - 0.5) * np.sqrt(12.0) * spec


class Mixture:
    """The corpus (``n`` rows) and held-out queries of one seed."""

    def __init__(self, seed: int, n: int, d: int):
        self.n, self.d = int(n), int(d)
        self.rows = chunk_rows(self.d)
        nc = max(32, self.n // 300)
        spec = 1.0 / np.arange(1, d + 1, dtype=np.float32)
        self.spec = jnp.asarray(spec / np.sqrt((spec**2).mean()))
        k_c, self.k_x, self.k_q = jax.random.split(base_key(seed), 3)
        centers = jax.random.normal(k_c, (nc, d), jnp.float32) * self.spec
        self.centers = centers / jnp.linalg.norm(centers, axis=1, keepdims=True)

    @property
    def n_chunks(self) -> int:
        return -(-self.n // self.rows)

    def corpus_chunk(self, i: int):
        """Device array of rows ``[i * rows, (i + 1) * rows)``; rows past
        ``n`` in the last chunk are drawn too and must be masked."""
        return _draw(jax.random.fold_in(self.k_x, i), self.centers,
                     self.spec, self.rows)

    def _to_host(self, key, m: int, n_chunks: int) -> np.ndarray:
        out = np.empty((m, self.d), np.float32)
        nxt = _draw(jax.random.fold_in(key, 0), self.centers, self.spec,
                    self.rows)
        for i in range(n_chunks):
            cur = nxt
            if i + 1 < n_chunks:  # the next draw runs while this one copies
                nxt = _draw(jax.random.fold_in(key, i + 1), self.centers,
                            self.spec, self.rows)
            s = i * self.rows
            e = min(s + self.rows, m)
            out[s:e] = np.asarray(cur)[: e - s]
        return out

    def corpus(self) -> np.ndarray:
        """The whole corpus as one host array (n, d) float32."""
        return self._to_host(self.k_x, self.n, self.n_chunks)

    def queries(self, m: int) -> np.ndarray:
        """``m`` held-out queries (host, float32), from the same mixture."""
        return self._to_host(self.k_q, m, -(-m // self.rows))
