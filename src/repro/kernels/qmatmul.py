"""Weight-only quantized matmul (w8a16 / w4a16) — the serving hot-spot.

TPU adaptation of the paper's bit-width lever (DESIGN.md Sec. 2): decode is
HBM-bandwidth-bound, so narrow *storage* is where arbitrary bit-width pays
off.  Weights live in HBM as int8 codes (or int4 pairs packed into int8);
each (bk, bn) block is unpacked in VMEM, converted to bf16 (exact for |code|
≤ 127), fed to the MXU against the bf16 activations, and the per-channel
scale is applied once to the f32 accumulator at the end (linearity — the
dequant multiply leaves the inner loop entirely).

Grid: ``(M/bm, N/bn, K/bk)``, K innermost; f32 VMEM scratch accumulator.
A packed int4 weight block is ``bk × bn`` bytes as stored (128 lanes wide,
as the TPU's tiling rule asks) and unpacks to ``2·bn`` output channels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mvau import unpack_int4_block


def _qmm_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, n_k: int, bits: int,
                out_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.bfloat16)
    w = w_ref[...]
    w_codes = unpack_int4_block(w) if bits == 4 else w.astype(jnp.int32)
    acc_ref[...] += jax.lax.dot_general(
        x, w_codes.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _scale():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(out_dtype)


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit,
                   static_argnames=("bits", "bm", "bn", "bk", "interpret"))
def qmatmul_pallas(x: jax.Array, w_codes: jax.Array, scale: jax.Array,
                   bits: int = 8, bm: int = 128, bn: int = 128, bk: int = 128,
                   interpret: bool = False) -> jax.Array:
    """``x @ dequant(w_codes)`` with per-output-channel scale.

    x: (M, K) bf16/f32; w_codes: (K, N) int8 when bits==8, (K, N//2) packed
    int8 when bits==4; scale: (N,) f32.  ``bn`` is the lane width of a
    weight block as stored.
    """
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    m, kdim = x.shape
    n = w_codes.shape[1] * (2 if bits == 4 else 1)
    if scale.shape != (n,):
        raise ValueError(f"scale must be ({n},), got {scale.shape}")
    out_dtype = x.dtype

    bn_out = 2 * bn if bits == 4 else bn   # output channels per weight block
    xp = _pad_to(_pad_to(x, 0, bm), 1, bk)
    wp = _pad_to(_pad_to(w_codes, 0, bk), 1, bn)
    sp = _pad_to(scale.astype(jnp.float32).reshape(1, n), 1, bn_out)
    mp, kp = xp.shape
    np_ = wp.shape[1] * (2 if bits == 4 else 1)
    grid = (mp // bm, np_ // bn_out, kp // bk)

    kernel = functools.partial(_qmm_kernel, n_k=grid[2], bits=bits,
                               out_dtype=out_dtype)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn_out), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn_out), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn_out), jnp.float32)],
        interpret=interpret,
    )(xp, wp, sp)
    return out[:m, :n]
