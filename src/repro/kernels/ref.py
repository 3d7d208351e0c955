"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantic ground truth: each kernel's test sweeps shapes/dtypes
and asserts allclose against the function here.  They are also the
"interpreted" execution path used in documentation examples.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import quant


def mvau(x: jax.Array, w: jax.Array, thresholds: jax.Array,
         out_base: int = 0, out_scale: float = 1.0,
         out_bias: float = 0.0) -> jax.Array:
    """Matrix-Vector-Activation Unit: ``threshold_count(x @ w)``.

    x: (..., K) float (values on a fixed-point grid), w: (K, N),
    thresholds: (L,) or (N, L).  Output: float32 codes
    ``out_scale * (out_base + Σᵢ 1[y ≥ Tᵢ]) + out_bias``.
    """
    y = jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    return quant.multithreshold(y, thresholds, out_base, out_scale, out_bias)


def mvau_int(x_codes: jax.Array, w_codes: jax.Array, thresholds_int: jax.Array,
             out_base: int = 0) -> jax.Array:
    """Integer-domain MVAU: integer codes, int32 accumulate, int thresholds.

    This is the FINN datapath proper — scales have been folded into the
    thresholds, so the arithmetic is exact integer compare-count
    (``threshold_counts`` binary-searches sorted constant tables, which
    keeps 16-bit activation grids — 65535 levels — tractable).
    """
    acc = jnp.matmul(x_codes.astype(jnp.int32), w_codes.astype(jnp.int32))
    counts = quant.threshold_counts(acc, thresholds_int)
    return (out_base + counts).astype(jnp.int32)


def matmul_int(x_codes: jax.Array, w_codes: jax.Array) -> jax.Array:
    """Bare integer-code matmul: int32 accumulate, int32 out."""
    return jnp.matmul(x_codes.astype(jnp.int32), w_codes.astype(jnp.int32))


# --------------------------------------------------------------------------
# Fast integer paths — bit-identical to the oracles above, chosen by the
# deploy-time dispatch (kernels/ops.py) from static node attrs.  The oracles
# stay deliberately naive; these carry the perf claim.
# --------------------------------------------------------------------------
def matmul_int_fast(x_codes: jax.Array, w_codes: jax.Array,
                    acc_f32_exact: bool = False) -> jax.Array:
    """Integer-code matmul through the backend's fast GEMM.

    Integer matmuls have no BLAS/MXU path on most backends (an int32
    ``jnp.matmul`` lowers to a naive loop on CPU — measured ~6× slower than
    SGEMM).  When the lowering proved every partial sum fits ±2**24
    (``acc_f32_exact``), computing the code matmul in f32 is EXACT: every
    intermediate is an integer exactly representable in the f32 mantissa,
    so the truncating cast back to int32 is the identity on the true sum.
    That needs f32 operands: a TPU's default precision rounds them to bf16,
    which is inexact above ±256, hence ``HIGHEST``.
    """
    if acc_f32_exact:
        acc = jnp.matmul(x_codes.astype(jnp.float32),
                         w_codes.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        return acc.astype(jnp.int32)
    return matmul_int(x_codes, w_codes)


def _counts_unrolled(acc: jax.Array, thresholds: jax.Array) -> jax.Array:
    """Per-level unrolled compare-count: L adds of a (..., N) compare.

    For small L this beats both the rank-3 dense compare (which
    materializes an (M, N, L) intermediate) and binary search (whose
    per-element gathers don't vectorize) — measured ~6× over dense at
    L = 15 on CPU.
    """
    counts = jnp.zeros(acc.shape, jnp.int32)
    for level in range(thresholds.shape[-1]):
        counts += (acc >= thresholds[..., level]).astype(jnp.int32)
    return counts


_UNROLL_MAX_LEVELS = 64   # above this, sorted tables binary-search instead


def threshold_counts_fast(acc: jax.Array,
                          thresholds_int: jax.Array) -> jax.Array:
    """``Σᵢ 1[acc ≥ Tᵢ]`` picking the fastest exact strategy for L.

    Small tables unroll (one vectorized compare per level); large sorted
    tables fall through to :func:`quant.threshold_counts`, which
    binary-searches concrete sorted tables — the fusion pass sorts every
    table it emits, so deployed graphs always hit one of the fast forms.
    """
    if thresholds_int.shape[-1] < _UNROLL_MAX_LEVELS \
            and not isinstance(thresholds_int, jax.core.Tracer):
        return _counts_unrolled(acc, jnp.asarray(thresholds_int))
    return quant.threshold_counts(acc, thresholds_int)


def mvau_int_fast(x_codes: jax.Array, w_codes: jax.Array,
                  thresholds_int: jax.Array, out_base: int = 0,
                  acc_f32_exact: bool = False) -> jax.Array:
    """Fused integer MVAU via the fast GEMM + fast threshold count.

    Bit-for-bit equal to :func:`mvau_int` (asserted in tests); this is the
    serving path for fused ``mvau_int`` nodes on backends without a
    compiled Pallas datapath.
    """
    acc = matmul_int_fast(x_codes, w_codes, acc_f32_exact)
    counts = threshold_counts_fast(acc, thresholds_int)
    return (out_base + counts).astype(jnp.int32)


def multithreshold_int(x_codes: jax.Array, thresholds_int: jax.Array,
                       out_base: int = 0) -> jax.Array:
    """Integer-domain MultiThreshold: ``base + Σᵢ 1[x ≥ Tᵢ]`` over int32
    codes with an int32 threshold table (scales already folded in)."""
    counts = quant.threshold_counts(x_codes.astype(jnp.int32), thresholds_int)
    return (out_base + counts).astype(jnp.int32)


def requantize(q: jax.Array, shift: int, bits: int, frac_bits: int,
               signed: bool = True) -> jax.Array:
    """Exact integer regrid: codes at scale ``2**-f1`` → codes at
    ``2**-(f1+shift)``, round-half-even, saturating — bit-for-bit equal to
    ``quantize(dequantize(q), spec)`` whenever the float round-trip is
    itself exact (|q| ≤ 2**24, enforced by the fusion pass).

    Downshifts split ``q = (q >> k) * 2**k + r`` and round the remainder to
    even; upshifts pre-clip so the left shift can never overflow int32.
    """
    spec = quant.FixedPointSpec(bits, frac_bits, signed)
    q = q.astype(jnp.int32)
    if shift >= 0:
        # largest/smallest codes whose shifted value is still in range; one
        # beyond them saturates, so pre-clipping to ±1 outside is exact
        hi_pre = spec.qmax >> shift
        lo_pre = -((-spec.qmin) >> shift)
        q = jnp.clip(q, lo_pre - 1, hi_pre + 1) << shift
        return jnp.clip(q, spec.qmin, spec.qmax)
    k = -shift
    q2 = q >> k                          # arithmetic shift: floor(q / 2**k)
    r = q - (q2 << k)                    # remainder in [0, 2**k)
    half = 1 << (k - 1)
    up = (r > half) | ((r == half) & ((q2 & 1) == 1))
    q2 = q2 + up.astype(jnp.int32)
    return jnp.clip(q2, spec.qmin, spec.qmax)


def qmatmul(x: jax.Array, w_codes: jax.Array, scale: jax.Array,
            bits: int = 8) -> jax.Array:
    """Weight-only quantized matmul: ``x @ (codes * scale)``.

    x: (..., K) bf16/f32; w_codes: int8 (K, N) for bits==8 or packed int4
    (K, N//2) for bits==4; scale: per-output-channel (N,) or scalar.

    Contract note: activations are consumed at **bf16** (MXU input
    precision); codes are exact in bf16 (|code| ≤ 127 < 2^8 mantissa).
    Accumulation is f32.
    """
    if bits == 4:
        w_int = quant.unpack_int4(w_codes)
    elif bits == 8:
        w_int = w_codes.astype(jnp.int32)
    else:
        raise ValueError(f"unsupported weight bits {bits}")
    x16 = x.astype(jnp.bfloat16).astype(jnp.float32)
    acc = jnp.matmul(x16, w_int.astype(jnp.float32))
    return (acc * scale).astype(x.dtype)


def gap(x: jax.Array) -> jax.Array:
    """GlobalAccPool: spatial **sum** (N,H,W,C) -> (N,C); no division
    (paper Sec. III-D) — integer inputs accumulate in int32."""
    if jnp.issubdtype(x.dtype, jnp.integer):
        return jnp.sum(x.astype(jnp.int32), axis=(1, 2))
    return jnp.sum(x.astype(jnp.float32), axis=(1, 2))


# ---------------------------------------------------------------------------
# Decode-workload attention (PR 10): shared by the graph interpreter, the
# compiled DeployedModel and models.lm.decode_step_ref — ONE definition so
# "bit-for-bit with the interpreter" is a property of the code, not a hope.
# All math is f32 — the einsums ask for HIGHEST precision, since a TPU's
# default rounds f32 operands to bf16 — and there is no GQA broadcast
# (callers assert n_kv_heads == n_heads).
# ---------------------------------------------------------------------------
_F32 = jax.lax.Precision.HIGHEST

def attn_decode(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                k_cache: jax.Array, v_cache: jax.Array, pos: jax.Array,
                heads: int):
    """One causal decode step over a fixed-capacity KV cache.

    q/k_new/v_new: (B, D) f32 projections for the CURRENT token;
    k_cache/v_cache: (B, C, D) with positions ``< pos`` filled;
    pos: (B,) int32 write/read position per row.  Returns
    ``(out (B, D), k_cache', v_cache')`` with the new K/V written at
    ``pos`` (functional update — the serving layer owns cache storage).
    """
    B, D = q.shape
    C = k_cache.shape[1]
    hd = D // heads
    slot = jnp.arange(C, dtype=jnp.int32)[None, :] == pos[:, None]  # (B, C)
    kc = jnp.where(slot[..., None], k_new[:, None, :].astype(k_cache.dtype),
                   k_cache)
    vc = jnp.where(slot[..., None], v_new[:, None, :].astype(v_cache.dtype),
                   v_cache)
    qh = q.astype(jnp.float32).reshape(B, heads, hd)
    kh = kc.astype(jnp.float32).reshape(B, C, heads, hd)
    vh = vc.astype(jnp.float32).reshape(B, C, heads, hd)
    s = jnp.einsum("bhd,bchd->bhc", qh, kh, precision=_F32) / math.sqrt(hd)
    live = jnp.arange(C, dtype=jnp.int32)[None, None, :] <= pos[:, None, None]
    s = jnp.where(live, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhc,bchd->bhd", w, vh, precision=_F32).reshape(B, D)
    return out.astype(q.dtype), kc, vc


def attn_prefill(q: jax.Array, k: jax.Array, v: jax.Array,
                 heads: int) -> jax.Array:
    """Causal self-attention over a whole prompt: q/k/v (B, S, D) f32."""
    B, S, D = q.shape
    hd = D // heads
    qh = q.astype(jnp.float32).reshape(B, S, heads, hd)
    kh = k.astype(jnp.float32).reshape(B, S, heads, hd)
    vh = v.astype(jnp.float32).reshape(B, S, heads, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", qh, kh, precision=_F32) / math.sqrt(hd)
    causal = (jnp.arange(S, dtype=jnp.int32)[None, :]
              <= jnp.arange(S, dtype=jnp.int32)[:, None])
    s = jnp.where(causal[None, None, :, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, vh,
                     precision=_F32).reshape(B, S, D)
    return out.astype(q.dtype)
