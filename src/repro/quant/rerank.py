"""Shared exact re-rank stage (fp32 originals -> exact candidate distances).

Every quantized candidate-generation path — the two-stage int8 scan
(``quant/twostage.py``) and the q8 HNSW beam (``core/plan.py``) — ends the
same way: a small per-lane candidate set must be re-scored against the
EXACT fp32 vectors so returned distances carry no quantization error.  This
module is that stage, lifted out of the scan executor so both engines (and
any future code path, e.g. PQ) share one implementation and one
host/device placement policy.

``ExactStore`` owns the fp32 originals (+ squared norms + key table) for one
partition; ``exact_candidate_distances`` scores a (b, C) candidate matrix
against it:

* ``mode='host'`` — density-adaptive numpy: when the candidate volume
  ``b * C`` rivals the store size N (the routed-batch regime), ONE dense
  BLAS gemm + a take_along_axis beats b*C row gathers; otherwise gather
  only the candidate rows.  Host placement keeps the originals
  mmap-friendly.
* ``mode='device'`` — a jitted gather + batched contraction against a
  lazily-uploaded device copy (lane counts padded by the caller so the
  trace set stays bounded).

Distance convention (``exact_from_dots``): lower is better; 'l2' OMITS the
per-query ||q||^2 constant (it cannot change any within-query ordering) —
the query executor adds it back once after its final merge, one (B, topk)
add instead of one per lane.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax.numpy as jnp
import numpy as np

import jax


def exact_from_dots(dots, n2, metric, xp=np):
    """Metric correction shared by every exact-rerank path (host dense, host
    gather, device gather): exact distance from raw <q, x> dots and ||x||^2.
    l2 omits the per-query ||q||^2 constant (see module docstring)."""
    if metric == "l2":
        return n2 - 2.0 * dots
    if metric == "cos":
        return -dots / xp.sqrt(xp.maximum(n2, 1e-24))
    return -dots  # ip


@partial(jax.jit, static_argnames=("metric",))
def _rerank_gather_dev(q, cand, vecs, norms2, metric):
    """Exact candidate distances from a device-resident fp32 store:
    gather only the candidate rows, one batched contraction."""
    g = jnp.take(vecs, cand, axis=0)  # (L, C, D)
    dots = jnp.einsum(
        "lcd,ld->lc", g, q, precision=jax.lax.Precision.HIGHEST
    )  # exact f32: a TPU's default is one bf16 pass
    return exact_from_dots(dots, jnp.take(norms2, cand), metric, xp=jnp)


class ExactStore:
    """fp32 originals + norms + keys for one partition's exact re-rank."""

    def __init__(self, vectors: np.ndarray, keys: Optional[np.ndarray] = None):
        self.vectors = np.asarray(vectors, np.float32)
        self.norms2 = np.einsum(
            "nd,nd->n", self.vectors, self.vectors
        ).astype(np.float32)
        self.keys = (
            np.asarray(keys, np.int64)
            if keys is not None
            else np.arange(len(self.vectors), dtype=np.int64)
        )
        self._dev_vecs = None
        self._dev_norms2 = None

    @property
    def size(self) -> int:
        return self.vectors.shape[0]

    def device(self):
        """Lazily-uploaded device copy (cached for the store's lifetime)."""
        if self._dev_vecs is None:
            self._dev_vecs = jnp.asarray(self.vectors)
            self._dev_norms2 = jnp.asarray(self.norms2)
        return self._dev_vecs, self._dev_norms2

    def nbytes(self) -> int:
        return int(self.vectors.nbytes) + int(self.norms2.nbytes)


def resolve_store_mode(rerank_store: str) -> str:
    """'auto' -> concrete placement: device on TPU, host elsewhere."""
    if rerank_store == "auto":
        return "device" if jax.default_backend() == "tpu" else "host"
    if rerank_store not in ("host", "device"):
        raise ValueError(
            f"rerank_store={rerank_store!r} — expected 'auto', 'host' "
            "or 'device'"
        )
    return rerank_store


# lanns: dims[b<=16_384, C<=1024, l_pad<=16_384]
def exact_candidate_distances(  # lanns: hotpath
    q: np.ndarray,
    cand: np.ndarray,
    store: ExactStore,
    metric: str,
    *,
    mode: str = "host",
    l_pad: Optional[int] = None,
) -> np.ndarray:
    """Exact distances (b, C) for candidate rows ``cand`` (b, C) of ``store``.

    ``q`` (b, d) must already be metric-prepped (normalized for 'cos',
    mips-augmented -> 'l2').  ``l_pad`` pads the device-mode lane count so
    the jitted gather reuses a bounded trace set; ignored for host mode.
    """
    b, C = cand.shape
    if mode == "device":
        vecs, n2 = store.device()
        qp = q
        cp = cand
        if l_pad is not None and l_pad != b:
            qp = np.zeros((l_pad, q.shape[1]), np.float32)
            qp[:b] = q
            cp = np.zeros((l_pad, C), np.int32)
            cp[:b] = cand
        ex = _rerank_gather_dev(  # lanns: noqa[LANNS033] -- callers pad l_pad on the quarter-pow2 grid (plan.py / twostage.py contract); this function never invents lane counts
            jnp.asarray(qp), jnp.asarray(cp), vecs, n2, metric  # lanns: noqa[LANNS033] -- same quarter-pow2 l_pad contract as the gather call above
        )
        return np.asarray(ex)[:b]  # lanns: noqa[LANNS003] -- the rerank stage's one designed sync (device mode)
    v, n2 = store.vectors, store.norms2
    if b * C >= store.size:  # dense regime: one BLAS gemm beats b*C gathers
        full = exact_from_dots(q @ v.T, n2[None, :], metric)
        return np.take_along_axis(full, cand, axis=1)
    g = np.take(v, cand.reshape(-1), axis=0).reshape(b, C, -1)
    dots = np.matmul(g, q[:, :, None])[:, :, 0]
    return exact_from_dots(dots, np.take(n2, cand), metric)
