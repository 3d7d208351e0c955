"""Nearest-Class-Mean classifier (paper Fig. 1 step 3, Fig. 5 CPU side).

The backbone (FPGA/TPU side) emits feature vectors; the NCM head lives on
the host: support features → per-class means; query features → nearest mean.
Features are L2-normalized first (the EASY recipe the paper builds on).

Accumulation order is CANONICAL: per-class sums are a strict left fold over
support rows in presentation order (``running_update``), so the online
:class:`repro.serve.PrototypeStore` — which receives the same rows in the
same order, possibly chunked across requests — reproduces ``class_means``
**bit-for-bit**.  f32 addition is not associative; a matmul-reduced sum
(the previous implementation) and a streaming sum would drift apart on
real feature vectors, and "deployed == offline" would silently become
"deployed ≈ offline".
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def _l2(x: jax.Array) -> jax.Array:
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-8)


def running_update(sums: jax.Array, counts: jax.Array, features: jax.Array,
                   labels: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Fold a chunk of support rows into per-class running ``(sums, counts)``.

    ``sums``: (W, D) f32 per-class sums of L2-normalized features;
    ``counts``: (W,) f32 per-class row counts;
    ``features``: (N, D) raw backbone features; ``labels``: (N,) way indices.

    Rows are added STRICTLY sequentially in presentation order (lax.scan),
    so folding one batch equals folding the same rows split across any
    number of chunks — the bit-for-bit contract the online store relies on.
    Each row is L2-normalized inside the step, as a (D,) vector: a norm
    taken over the whole (N, D) chunk is free to vectorize differently for
    each N, which moves the last bit with the chunking.
    """
    labels = labels.astype(jnp.int32)

    def step(carry, xs):
        s, c = carry
        row, lab = xs
        return (s.at[lab].add(_l2(row)), c.at[lab].add(1.0)), None

    (sums, counts), _ = jax.lax.scan(
        step, (sums, counts), (features.astype(jnp.float32), labels))
    return sums, counts


def finalize_means(sums: jax.Array, counts: jax.Array) -> jax.Array:
    """(W, D) running sums + (W,) counts -> (W, D) L2-normalized means."""
    return _l2(sums / jnp.maximum(counts[:, None], 1.0))


def class_means(features: jax.Array, labels: jax.Array, n_way: int
                ) -> jax.Array:
    """(N, D) support features + (N,) way-labels -> (n_way, D) means."""
    d = features.shape[-1]
    sums = jnp.zeros((n_way, d), jnp.float32)
    counts = jnp.zeros((n_way,), jnp.float32)
    sums, counts = running_update(sums, counts, features, labels)
    return finalize_means(sums, counts)


_traces = [0]          # traces of the head; see trace_count()


@jax.jit
def cosine_sims(query_features: jax.Array, means: jax.Array) -> jax.Array:
    """(Q, D) queries x (C, D) normalized means -> (Q, C) cosine sims.

    An elementwise product reduced over D, not a matmul: each similarity is
    then one f32 reduction of D f32 products, in an order XLA's CPU backend
    keeps for every Q and C.  A GEMM picks its kernel, and so its summation
    order, from the shapes (a one-row block becomes a matrix-vector
    product), and on a TPU it rounds f32 operands to bf16 at default
    precision; either would make a padded, bucketed or sharded head differ
    from the offline one in the last bits.  On a TPU v5e the reduction (and
    the norm of ``_l2``) still moves the last bit when Q or C is 1, by up
    to 3e-8.  Jitted, so an eager caller runs the same fused program as a
    traced one.
    """
    _traces[0] += 1            # runs at trace time only (jit above)
    q = _l2(query_features.astype(jnp.float32))
    return jnp.sum(q[:, None, :] * means.astype(jnp.float32)[None, :, :],
                   axis=-1)


def trace_count() -> int:
    """How often this process traced :func:`cosine_sims`: once per new
    (queries, classes, feature dim) shape, wherever it is called from (the
    serving store, the sharded head, the offline episodes)."""
    return _traces[0]


def ncm_classify(query_features: jax.Array, means: jax.Array) -> jax.Array:
    """Nearest mean in cosine distance (== L2 on normalized vectors)."""
    return jnp.argmax(cosine_sims(query_features, means), axis=-1)


def ncm_accuracy(query_features: jax.Array, query_labels: jax.Array,
                 support_features: jax.Array, support_labels: jax.Array,
                 n_way: int) -> jax.Array:
    means = class_means(support_features, support_labels, n_way)
    pred = ncm_classify(query_features, means)
    return (pred == query_labels).mean()
