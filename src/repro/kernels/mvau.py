"""Pallas MVAU — the TPU adaptation of FINN's Matrix-Vector-Activation Unit.

FINN's MVAU streams BRAM-resident weights through an integer MAC array and
applies MultiThreshold activation in the same pipeline stage, never touching
DRAM between matmul and activation.  The TPU analogue implemented here:

* weights tile HBM→VMEM once per (bn, bk) block (BlockSpec pipeline — Pallas
  double-buffers automatically), the MXU consumes them at int8/bf16,
* the int32/f32 accumulator lives in a VMEM scratch across the K grid axis,
* MultiThreshold (compare-count against the per-channel threshold block) runs
  on the VPU *before* the tile is written back — matmul and activation fuse
  exactly as in the FINN dataflow edge, eliminating the HBM round-trip of the
  intermediate.

Two kernels:
  ``mvau_int_pallas``  int8 × int8 → int32 accumulate, int32 thresholds
                       (the FINN path proper, on the int8 MXU)
  ``mvau_pallas``      f32 × f32 → f32 accumulate at full precision, f32
                       thresholds (QAT-grid floats)

Grid: ``(M/bm, N/bn, K/bk)`` with K innermost (sequential accumulation).
Threshold tables enter the kernels transposed, as ``(L, N)``: each level is
then one ``(1, bn)`` row that broadcasts over the ``bm`` accumulator rows, so
the compare-count needs no ``(bm, bn, L)`` slab and VMEM stays bounded at any
L (8-bit activations have L = 255).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _count_levels(acc: jax.Array, t_ref, n_levels: int) -> jax.Array:
    """``Σ_l 1[acc ≥ t_l]`` for a (bm, bn) accumulator against an (L, bn)
    threshold block, one level row at a time."""
    def level(i, counts):
        return counts + (acc >= t_ref[pl.ds(i, 1), :]).astype(jnp.int32)

    return jax.lax.fori_loop(0, n_levels, level,
                             jnp.zeros(acc.shape, jnp.int32))


def _mvau_kernel(x_ref, w_ref, t_ref, o_ref, acc_ref, *,
                 n_k: int, n_levels: int, out_base: float, out_scale: float,
                 out_bias: float):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # f32 products at full precision: a TPU's default rounds f32 operands
    # to bf16, which is inexact for grid values wider than 8 bits
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)

    @pl.when(k == n_k - 1)
    def _activate():
        counts = _count_levels(acc_ref[...], t_ref, n_levels)
        y = out_scale * (out_base + counts.astype(jnp.float32)) + out_bias
        o_ref[...] = y


def unpack_int4_block(w: jax.Array) -> jax.Array:
    """In-register nibble unpack: packed (bk, bn) int8 → (bk, 2·bn) int32
    codes in [-8, 7].

    Low nibble holds the even output channel (quant.pack_int4's layout);
    the shift pairs sign-extend each nibble.  Runs on the VPU inside the
    kernel, so packed weights go HBM→VMEM at half the bytes and never exist
    unpacked outside VMEM.
    """
    p = w.astype(jnp.int32)
    lo = (p << 28) >> 28
    hi = (p << 24) >> 28
    return jnp.stack([lo, hi], axis=-1).reshape(w.shape[0], w.shape[1] * 2)


def _mvau_int_kernel(x_ref, w_ref, t_ref, o_ref, acc_ref, *,
                     n_k: int, n_levels: int, out_base: int, w_packed: bool):
    """Integer MVAU writing int32 codes: the FINN datapath proper.

    The int32 accumulator lives in VMEM scratch across the K grid axis; on
    the last K step the sorted per-channel threshold table is applied
    in-register (level-by-level compare-count — FINN's unary thresholding,
    exactly what the HW MVAU does) and only the narrow output code is
    written back.  The wide accumulator never touches HBM.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...]
    if w_packed:
        w = unpack_int4_block(w).astype(jnp.int8)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _activate():
        o_ref[...] = out_base + _count_levels(acc_ref[...], t_ref, n_levels)


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _thresholds_lanes(thresholds: jax.Array, n_pad: int, big) -> jax.Array:
    """(N, L) table -> (L, N + n_pad): levels on sublanes, channels on
    lanes; padded channels get a threshold no accumulator reaches."""
    return jnp.pad(thresholds.T, ((0, 0), (0, n_pad)), constant_values=big)


@functools.partial(
    jax.jit,
    static_argnames=("out_base", "out_scale", "out_bias", "bm", "bn", "bk",
                     "interpret"))
def mvau_pallas(x: jax.Array, w: jax.Array, thresholds: jax.Array,
                out_base: float = 0.0, out_scale: float = 1.0,
                out_bias: float = 0.0, bm: int = 128, bn: int = 128,
                bk: int = 128, interpret: bool = False) -> jax.Array:
    """Fused ``multithreshold(x @ w)`` on f32 grid values; see module
    docstring.

    x: (M, K) f32; w: (K, N) f32; thresholds: (N, L) f32 (per-tensor (L,)
    is broadcast by the ops.py wrapper).  Output: (M, N) f32.
    """
    if x.ndim != 2 or w.ndim != 2 or thresholds.ndim != 2:
        raise ValueError("mvau_pallas expects 2-D x, w and (N, L) thresholds")
    m, _ = x.shape
    n = w.shape[1]
    n_levels = thresholds.shape[1]

    # Pad to block multiples (K zero-pad is exact for matmul; padded N/M
    # rows/cols are sliced off below; +inf thresholds keep padded-channel
    # counts at zero rather than garbage).
    xp = _pad_to(_pad_to(x, 0, bm), 1, bk)
    wp = _pad_to(_pad_to(w, 0, bk), 1, bn)
    tp = _thresholds_lanes(thresholds, wp.shape[1] - n, jnp.inf)
    mp, kp = xp.shape
    np_ = wp.shape[1]
    grid = (mp // bm, np_ // bn, kp // bk)

    kernel = functools.partial(
        _mvau_kernel, n_k=grid[2], n_levels=n_levels, out_base=float(out_base),
        out_scale=float(out_scale), out_bias=float(out_bias))

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((n_levels, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xp, wp, tp)
    return out[:m, :n]


@functools.partial(
    jax.jit,
    static_argnames=("out_base", "w_packed", "bm", "bn", "bk", "interpret"))
def mvau_int_pallas(x: jax.Array, w: jax.Array, thresholds_int: jax.Array,
                    out_base: int = 0, w_packed: bool = False,
                    bm: int = 128, bn: int = 128, bk: int = 128,
                    interpret: bool = False) -> jax.Array:
    """Fused integer MVAU on the int8 MXU: int32 code output.

    x: (M, K) int8 codes; w: (K, N) int8 codes, or (K, N//2) packed int4
    pairs (``w_packed=True``: unpacked to int8 in VMEM, never materialized
    in HBM); thresholds_int: (N, L) sorted int32.  Output: (M, N) int32
    codes ``out_base + Σᵢ 1[acc ≥ Tᵢ]``.  ``bn`` is the lane width of a
    weight block as stored, so a packed block yields ``2·bn`` channels.
    Wider codes have no MXU path; ``ops.kernel_dispatch`` keeps them off
    this kernel.
    """
    if x.ndim != 2 or w.ndim != 2 or thresholds_int.ndim != 2:
        raise ValueError(
            "mvau_int_pallas expects 2-D x, w and (N, L) thresholds")
    if x.dtype != jnp.int8 or w.dtype != jnp.int8:
        raise TypeError(f"mvau_int_pallas takes int8 codes, got x "
                        f"{x.dtype} and w {w.dtype}")
    m, _ = x.shape
    n = w.shape[1] * (2 if w_packed else 1)
    n_levels = thresholds_int.shape[1]
    bn_out = 2 * bn if w_packed else bn

    xp = _pad_to(_pad_to(x, 0, bm), 1, bk)
    wp = _pad_to(_pad_to(w, 0, bk), 1, bn)
    mp, kp = xp.shape
    np_ = wp.shape[1] * (2 if w_packed else 1)
    tp = _thresholds_lanes(thresholds_int, np_ - n, jnp.iinfo(jnp.int32).max)
    grid = (mp // bm, np_ // bn_out, kp // bk)

    kernel = functools.partial(
        _mvau_int_kernel, n_k=grid[2], n_levels=n_levels,
        out_base=int(out_base), w_packed=w_packed)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((n_levels, bn_out), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn_out), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn_out), jnp.int32)],
        interpret=interpret,
    )(xp, wp, tp)
    return out[:m, :n]
