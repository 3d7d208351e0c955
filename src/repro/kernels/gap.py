"""Pallas GlobalAccPool — paper Sec. III-D, as a kernel.

FINN's GlobalAccPool replaces ReduceMean: it emits the **integer spatial
sum** and leaves the 1/(H·W) scale to a downstream Mul that streamline folds
away.  On TPU the same shape: accumulate the (H·W, C) feature map into a
(1, C) VMEM register tile in int32 (exact for integer codes), never dividing
in the datapath.

Grid: ``(N, HW/bhw)`` — one image per grid row, spatial chunks innermost.
The output is laid out ``(N, 1, C)`` so each image's block is a whole
``(1, C)`` plane: a ``(1, C)`` block of an ``(N, C)`` array would break the
TPU's (8, 128) tiling rule for every N > 1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gap_kernel(x_ref, o_ref, acc_ref, *, n_hw: int, int_path: bool):
    h = pl.program_id(1)

    @pl.when(h == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]  # (bhw, C)
    if int_path:
        acc_ref[...] += jnp.sum(x.astype(jnp.int32), axis=0, keepdims=True)
    else:
        acc_ref[...] += jnp.sum(x.astype(jnp.float32), axis=0, keepdims=True)

    @pl.when(h == n_hw - 1)
    def _emit():
        o_ref[0] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bhw", "interpret"))
def gap_pallas(x: jax.Array, bhw: int = 256, interpret: bool = False) -> jax.Array:
    """(N, H, W, C) -> (N, C) spatial sum (no division — see module doc)."""
    n, h, w, c = x.shape
    int_path = jnp.issubdtype(x.dtype, jnp.integer)
    out_dtype = jnp.int32 if int_path else jnp.float32
    xf = x.reshape(n, h * w, c)
    bhw = min(bhw, -(-(h * w) // 8) * 8)      # a sublane multiple
    pad = (-xf.shape[1]) % bhw
    if pad:
        xf = jnp.pad(xf, ((0, 0), (0, pad), (0, 0)))
    hw = xf.shape[1]
    grid = (n, hw // bhw)
    kernel = functools.partial(_gap_kernel, n_hw=grid[1], int_path=int_path)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, bhw, c), lambda i, j: (i, j, 0))],
        out_specs=pl.BlockSpec((1, 1, c), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1, c), out_dtype),
        scratch_shapes=[pltpu.VMEM((1, c), out_dtype)],
        interpret=interpret,
    )(xf).reshape(n, c)
