"""Pallas bidirectional attention on activation codes — a ViT block's
multi-head softmax attention, integer throughout.

q, k and v arrive on the activation grid (8-bit codes, as float32 values).
Scores are code products, the softmax is I-BERT's integer exp with weights
floored onto ``2**-16`` (:func:`repro.kernels.ref.iexp_consts`), and the
output is the product of weights and v codes.  Every product takes
bfloat16 operands that hold the integers exactly (the weights split into
their high and low bytes) with float32 sums below ``2**24``, and every other
step is integer, so the kernel gives its oracle's bits, in any order of
evaluation: :func:`repro.kernels.ref.attn_full`.

One program per batch row holds the row's whole ``(S, 3D)`` q|k|v block in
VMEM and writes its ``(S, D)`` output: the scores of a 197-token image never
leave VMEM.  Heads are taken a lane group at a time: a group is 128 lanes
(a pair of 64-wide heads) where the width allows, so every slice of the
block is lane-aligned; within a group each head's scores and output come
from its own lanes, the other heads' lanes zeroed (an exact zero in every
sum; the MXU pass costs the same at 64 or 128 deep).

S is padded to a multiple of 8 (the f32 sublane tile); padded keys score
far below every real one, so their exp terms are 0, and padded query rows
are sliced off.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import PROB_BITS, iexp_consts

__all__ = ["attn_full_pallas"]

_SUBLANES = 8
_FAR = -2.0 ** 30        # a padded key's score: past n_max below any real one


def _lane_group(d: int, hd: int) -> int:
    """Lanes taken per step: 128 (whole heads) where ``d`` tiles into 128-lane
    groups, else all of ``d``."""
    if d % 128 == 0 and 128 % hd == 0:
        return 128
    return d


def _dot(a, b, dims):
    """Exact product of integer-valued operands (see the module docstring)."""
    return jax.lax.dot_general(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                               (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _floor_div(a, b):
    """``a // b`` for int32 ``0 <= a < 2**30``, ``0 < b``: a float32 estimate
    within one of the quotient, corrected in integers.  (Mosaic expands an
    int32 ``//`` into a long sequence: with it this kernel took 5.8-7.0 s
    to compile for a described v5e, against 1.5-2.3 s.)"""
    q = (a.astype(jnp.float32) / b.astype(jnp.float32)).astype(jnp.int32)
    r = a - q * b
    q = jnp.where(r < 0, q - 1, q)
    r = jnp.where(r < 0, r + b, r)
    return jnp.where(r >= b, q + 1, q)


def _attn_kernel(qkv_ref, o_ref, *, d: int, hd: int, seq: int,
                 frac_bits: int):
    s_pad = qkv_ref.shape[1]
    gw = _lane_group(d, hd)
    q_ln2, q_b, q_c, shift, n_max = iexp_consts(frac_bits, hd)
    lane = jax.lax.broadcasted_iota(jnp.int32, (s_pad, gw), 1)
    key = jax.lax.broadcasted_iota(jnp.int32, (s_pad, s_pad), 1)
    grid = 2.0 ** frac_bits
    for g in range(d // gw):
        lo = g * gw
        q = qkv_ref[0, :, lo:lo + gw] * grid
        k = qkv_ref[0, :, d + lo:d + lo + gw] * grid
        v = qkv_ref[0, :, 2 * d + lo:2 * d + lo + gw] * grid
        out = jnp.zeros((s_pad, gw), jnp.float32)
        for h in range(gw // hd):
            mine = (lane >= h * hd) & (lane < (h + 1) * hd)
            s = _dot(jnp.where(mine, q, 0.0), k, ((1,), (1,)))
            if seq < s_pad:
                s = jnp.where(key < seq, s, _FAR)
            n = (jnp.max(s, axis=-1, keepdims=True) - s).astype(jnp.int32)
            n = jnp.minimum(n, n_max)
            z = _floor_div(n, jnp.full_like(n, q_ln2))
            t = q_b - (n - z * q_ln2)
            e = jax.lax.shift_right_arithmetic(t * t + q_c, z + shift)
            total = jnp.sum(e.astype(jnp.float32), axis=-1,
                            keepdims=True).astype(jnp.int32)
            p = _floor_div(e << PROB_BITS, jnp.broadcast_to(total, e.shape))
            vh = jnp.where(mine, v, 0.0)
            out = out + (256.0 * _dot(p >> 8, vh, ((1,), (0,)))
                         + _dot(p & 255, vh, ((1,), (0,))))
        o_ref[0, :, lo:lo + gw] = out * 2.0 ** -(PROB_BITS + frac_bits)


@functools.partial(jax.jit,
                   static_argnames=("heads", "frac_bits", "interpret"))
def attn_full_pallas(qkv: jax.Array, heads: int, frac_bits: int,
                     interpret: bool = False) -> jax.Array:
    """Bidirectional multi-head attention: ``qkv`` (B, S, 3D) float32 on the
    activation grid of ``frac_bits`` fraction bits, q|k|v side by side with
    heads in order within each -> (B, S, D) float32."""
    if qkv.ndim != 3 or qkv.shape[-1] % 3:
        raise ValueError(f"attn_full_pallas expects (B, S, 3D), got "
                         f"{qkv.shape}")
    b, seq, d3 = qkv.shape
    d = d3 // 3
    if d % heads:
        raise ValueError(f"width {d} does not split into {heads} heads")
    if seq > 512:
        raise ValueError(f"{seq} tokens: the weights' low bytes times v "
                         f"codes may sum past 2**24")
    s_pad = -(-seq // _SUBLANES) * _SUBLANES
    x = qkv.astype(jnp.float32)
    if s_pad != seq:
        x = jnp.pad(x, ((0, 0), (0, s_pad - seq), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_attn_kernel, d=d, hd=d // heads, seq=seq,
                          frac_bits=frac_bits),
        grid=(b,),
        in_specs=[pl.BlockSpec((1, s_pad, d3), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, s_pad, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s_pad, d), jnp.float32),
        interpret=interpret,
        name="attn_full_pallas",
    )(x)
    return out[:, :seq]
