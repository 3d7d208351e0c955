"""Online prototype store — the paper's real-time few-shot loop as state.

Support shots arrive at runtime; ``register(class_id, features)`` folds them
into per-class running ``(sum, count)`` and the class is immediately
servable — no retraining, no retracing, no batch recompute.  The folds go
through :func:`repro.fsl.ncm.running_update`, the SAME strict left fold
``class_means`` uses, so the online store is **bit-for-bit** equal to an
offline NCM over the concatenated support set presented in the same order
(tested in ``tests/test_serve.py`` including single-shot and imbalanced
episodes).  Per-class accumulators are independent rows, so interleaving
registrations ACROSS classes cannot perturb any class's prototype.

The store holds features, not images: the engine runs the backbone (any
artifact of the registry), then routes feature rows here.  One store per
artifact — features from different bit-width datapaths live on different
numeric grids and must never share prototypes.
"""

from __future__ import annotations

import threading
from typing import Dict, Hashable, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.fsl import ncm

__all__ = ["PrototypeStore"]


class PrototypeStore:
    """Thread-safe incremental Nearest-Class-Mean state.

    ``register`` is O(shots + C) and rebuilds the cached prototype matrix
    eagerly — registrations are onboarding, classifies are the latency
    path, so the finalize cost (including its one-off per-shape XLA
    compile) must never land on a classify.  ``classify`` is one (Q, C)
    similarity with the query rows padded to a power-of-two bucket, the
    same shape discipline the engine applies to backbone batches: the set
    of head programs XLA ever compiles is bounded and :meth:`prime` can
    build them ahead of traffic.
    """

    def __init__(self):
        self._lock = threading.RLock()
        self._sums: Dict[Hashable, np.ndarray] = {}     # class -> (D,) f32
        self._counts: Dict[Hashable, int] = {}
        self._order: List[Hashable] = []                # registration order
        self._means: Optional[np.ndarray] = None        # cache, (C, D)

    def __len__(self) -> int:
        with self._lock:
            return len(self._order)

    @property
    def class_ids(self) -> Tuple[Hashable, ...]:
        with self._lock:
            return tuple(self._order)

    def counts(self) -> Dict[Hashable, int]:
        with self._lock:
            return dict(self._counts)

    def register(self, class_id: Hashable, features) -> int:
        """Fold (k, D) backbone features into ``class_id``'s running mean;
        returns the class's new shot count.  A 1-D (D,) single shot is
        accepted as (1, D)."""
        f = np.asarray(features, np.float32)
        if f.ndim == 1:
            f = f[None, :]
        if f.ndim != 2 or f.shape[0] == 0:
            raise ValueError(f"features must be (k, D) with k >= 1, "
                             f"got shape {f.shape}")
        with self._lock:
            if class_id not in self._sums:
                self._sums[class_id] = np.zeros((f.shape[1],), np.float32)
                self._counts[class_id] = 0
                self._order.append(class_id)
            elif self._sums[class_id].shape[0] != f.shape[1]:
                raise ValueError(
                    f"feature dim {f.shape[1]} != store dim "
                    f"{self._sums[class_id].shape[0]} for class {class_id!r}")
            # one-row view of the canonical fold: labels are all 0, the
            # (1, D)/(1,) carry is this class's accumulator
            sums, counts = ncm.running_update(
                jnp.asarray(self._sums[class_id][None, :]),
                jnp.asarray([float(self._counts[class_id])]),
                jnp.asarray(f), jnp.zeros((f.shape[0],), jnp.int32))
            self._sums[class_id] = np.asarray(sums[0])
            self._counts[class_id] = int(np.asarray(counts[0]))
            self._rebuild_locked()
            return self._counts[class_id]

    def _rebuild_locked(self) -> None:
        sums = jnp.asarray(np.stack([self._sums[c] for c in self._order]))
        counts = jnp.asarray([float(self._counts[c]) for c in self._order])
        self._means = np.asarray(ncm.finalize_means(sums, counts))

    def prototypes(self) -> Tuple[np.ndarray, Tuple[Hashable, ...]]:
        """(C, D) L2-normalized class means + matching class ids, in
        registration order (the store's stable way-index contract)."""
        with self._lock:
            if not self._order:
                raise RuntimeError("no classes registered yet")
            if self._means is None:
                self._rebuild_locked()
            return self._means, tuple(self._order)

    def _sims(self, q: np.ndarray, means: np.ndarray) -> np.ndarray:
        # the offline head's own function, so a served batch agrees bitwise
        # with ncm_classify over the same rows
        return np.asarray(ncm.cosine_sims(jnp.asarray(q), jnp.asarray(means)))

    def classify(self, query_features
                 ) -> Tuple[List[Hashable], np.ndarray]:
        """NCM over the current store: (n, D) queries -> (class ids, (n, C)
        cosine similarities).  A 1-D query is accepted as one row.

        Query rows pad to a power-of-two bucket (sliced back before the
        argmax) — every head op is per-row independent, so the padded
        program's live rows are bit-for-bit the unpadded ones, and the
        bounded shape set means no request ever stalls on an XLA compile
        once :meth:`prime` (or earlier traffic) built its bucket."""
        q = np.asarray(query_features, np.float32)
        if q.ndim == 1:
            q = q[None, :]
        means, ids = self.prototypes()
        n = q.shape[0]
        nb = 1 << max(n - 1, 0).bit_length()
        if nb != n:
            q = np.concatenate(
                [q, np.zeros((nb - n, q.shape[1]), np.float32)])
        sims = self._sims(q, means)[:n]
        pred = sims.argmax(axis=-1)
        return [ids[int(i)] for i in pred], sims

    def prime(self, dim: int, buckets: Sequence[int] = (1,)) -> None:
        """Build the classify head's per-bucket programs ahead of traffic
        (the engine calls this from warmup with its backbone bucket set).
        Without it, a fresh process's first classify stalls ~100 ms on
        eager XLA compiles of the head ops even when every backbone
        executable came out of the compile cache.  Uses the current
        prototype matrix when classes exist, a (1, D) dummy otherwise —
        a later first-use C still compiles once, but that matmul is the
        small residue, not the full head."""
        try:
            means, _ = self.prototypes()
        except RuntimeError:
            means = np.zeros((1, int(dim)), np.float32)
        for nb in sorted({int(b) for b in buckets} | {1}):
            if nb >= 1:
                self._sims(np.zeros((nb, int(dim)), np.float32), means)

    def reset(self) -> None:
        with self._lock:
            self._sums.clear()
            self._counts.clear()
            self._order.clear()
            self._means = None
