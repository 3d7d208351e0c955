"""GPipe-style pipeline parallelism via ``shard_map`` + ``ppermute``.

Each device on the ``"pipe"`` mesh axis owns one stage's weights.  Microbatches
enter stage 0 one per tick; activations rotate one hop per tick around the
ring; results exit the last stage after ``n_stages - 1`` fill ticks.  Total
schedule length is ``n_micro + n_stages - 1`` ticks — the classic GPipe
bubble.  Forward and backward are both exact (the test asserts fwd and grad
equality against a sequential apply): ``ppermute`` is linear, so autodiff
transposes the ring into the reverse rotation.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, ws: jax.Array, x: jax.Array,
                   mesh: Mesh, axis: str = None) -> jax.Array:
    """Apply ``n_stages`` stages to ``n_micro`` microbatches over a pipeline.

    Args:
      stage_fn: ``(w, activation) -> activation`` (shape-preserving).
      ws: stacked per-stage weights, leading dim ``n_stages``.
      x: microbatched input ``(n_micro, mb, ...)``.
      mesh: 1-D mesh whose axis carries the stages.
      axis: mesh axis name (defaults to the mesh's first axis).

    Returns the output of the final stage for every microbatch, in order,
    replicated across the mesh.
    """
    axis = axis or mesh.axis_names[0]
    n_stages = ws.shape[0]
    if mesh.shape[axis] != n_stages:
        raise ValueError(
            f"{n_stages} stages need a {n_stages}-wide '{axis}' axis, "
            f"got {mesh.shape[axis]}")
    n_micro = x.shape[0]
    n_ticks = n_micro + n_stages - 1

    def worker(w_local, x_all):
        w = jax.tree.map(lambda l: l[0], w_local)     # this device's stage
        stage_id = jax.lax.axis_index(axis)
        is_first = stage_id == 0
        is_last = stage_id == n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            state, outs = carry
            feed = x_all[jnp.minimum(t, n_micro - 1)]
            state = jnp.where(is_first, feed, state)
            y = stage_fn(w, state)
            mb_idx = t - (n_stages - 1)
            written = jax.lax.dynamic_update_slice(
                outs, y[None], (jnp.maximum(mb_idx, 0),) + (0,) * y.ndim)
            outs = jnp.where(jnp.logical_and(is_last, mb_idx >= 0),
                             written, outs)
            state = jax.lax.ppermute(y, axis, perm)
            return (state, outs), None

        init = (jnp.zeros_like(x_all[0]), jnp.zeros_like(x_all))
        (_, outs), _ = jax.lax.scan(tick, init, jnp.arange(n_ticks))
        # only the last stage holds real outputs; sum-broadcast to all
        return jax.lax.psum(jnp.where(is_last, outs, 0), axis)

    return jax.shard_map(worker, mesh=mesh, in_specs=(P(axis), P()),
                         out_specs=P(), check_vma=False)(ws, x)
