"""Sharded NCM head — prototype rows spread across devices, backbone
replicated.

At "many tenants × many classes" scale the (Q, C) similarity against the
prototype matrix is the part of serving that grows without bound: the
backbone batch is capped by ``max_batch``, but C = Σ classes over tenants
keeps climbing.  The classic cut (and the one ``repro/dist`` was built
for): replicate the small backbone everywhere, shard the big *state* — a
``shard_map`` over a 1-D device mesh gives every device a block of
prototype ROWS, each device computes its (Q, C/ndev) similarity block
against the replicated queries, and the blocks concatenate along the class
axis.  Row-block sharding never splits a reduction: every similarity is
still one dot product over the full feature dim on one device, so the
sharded head is **bit-for-bit** equal to the serial one — sharding moves
work, never numerics (the ``repro.dist`` contract).  On four TPU v5e chips
this holds at two rows per chip (``chip_smoke.py --chips 4``); a one-row
block can move the last bit there (see :func:`repro.fsl.ncm.cosine_sims`).

On a single device :func:`repro.dist.sharding.serve_mesh` returns ``None``
and the head degrades to the exact serial computation the
:class:`~repro.serve.store.PrototypeStore` does — tests pass anywhere, and
the cluster layer needs no device-count branches of its own.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist import act_sharding
from repro.dist.sharding import prototype_spec, serve_mesh
from repro.fsl import ncm
from repro.serve.store import PrototypeStore

__all__ = ["ShardedNCMHead", "ShardedStore"]


class ShardedNCMHead:
    """Batched NCM similarity with class/tenant prototype rows sharded
    across devices.

    ``sims(queries, means)`` pads the prototype rows up to a multiple of
    the device count, runs the ``shard_map`` program (queries replicated —
    constrained through the ``"serve/query_rows"`` act-sharding point —
    prototype rows split over the mesh axis), and slices the padding back
    off.  With one device (or ``devices=[...]`` of length 1) every call
    takes the serial path instead.
    """

    AXIS = "model"
    QUERY_RULE = "serve/query_rows"

    def __init__(self, devices: Optional[List] = None):
        self.mesh = serve_mesh(devices)
        self.n_dev = 1 if self.mesh is None else self.mesh.shape[self.AXIS]
        self._serial = jax.jit(ncm.cosine_sims)
        self._sharded = None
        if self.mesh is not None:
            mesh = self.mesh

            @partial(jax.shard_map, mesh=mesh,
                     in_specs=(P(), P(self.AXIS, None)),
                     out_specs=P(None, self.AXIS))
            def blocks(q, m_block):
                # per-device: full-D dots against this device's row block —
                # identical per-element reduction to the serial head
                return ncm.cosine_sims(q, m_block)

            def sharded(q, m):
                q = act_sharding.constrain(q, self.QUERY_RULE)
                return blocks(q, m)

            self._sharded = jax.jit(sharded)

    def place(self, means) -> jax.Array:
        """The (C, D) prototype rows zero-padded to a multiple of the device
        count and split by rows over the mesh — the operand the sharded
        program reads (on one device: the rows as they are)."""
        m = jnp.asarray(means, jnp.float32)
        if self.mesh is None:
            return m
        pad = (-m.shape[0]) % self.n_dev
        if pad:
            m = jnp.concatenate(
                [m, jnp.zeros((pad, m.shape[1]), m.dtype)], axis=0)
        return jax.device_put(
            m, NamedSharding(self.mesh,
                             prototype_spec(int(m.shape[0]), self.mesh)))

    def sims(self, query_features, means) -> np.ndarray:
        """(Q, D) queries × (C, D) prototype means -> (Q, C) cosine sims,
        bit-for-bit equal to the serial ``ncm.cosine_sims``."""
        q = jnp.asarray(query_features, jnp.float32)
        c = np.shape(means)[0]
        if self.mesh is None or c == 0:
            return np.asarray(self._serial(q, jnp.asarray(means, jnp.float32)))
        # bind the replicated-queries rule for the trace; the constraint is
        # the identity when unbound, so this is a layout hint, not a
        # correctness dependency
        rule = NamedSharding(self.mesh, P())
        with act_sharding.rules({self.QUERY_RULE: rule}):
            out = self._sharded(q, self.place(means))
        return np.asarray(out[:, :c])


class ShardedStore(PrototypeStore):
    """A :class:`PrototypeStore` whose ``classify`` runs through a
    :class:`ShardedNCMHead`.

    Registration (the bit-for-bit incremental fold) is untouched — the
    canonical left fold is tenant state, not compute to shard — and
    ``classify`` stays bitwise equal to the serial store because row-block
    sharding preserves every reduction (asserted in tests on 1 and N
    devices)."""

    def __init__(self, head: ShardedNCMHead):
        super().__init__()
        self.head = head

    def _sims(self, q, means):
        # classify/prime inherit the base's row bucketing and hit the
        # shared head's jitted programs here
        return self.head.sims(q, means)
