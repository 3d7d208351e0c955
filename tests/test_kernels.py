"""Per-kernel correctness: Pallas (interpret=True) vs the ref.py oracles,
swept over shapes and dtypes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def _rand(shape, lo=-2.0, hi=2.0):
    return RNG.uniform(lo, hi, size=shape).astype(np.float32)


# ---------------------------------------------------------------------------
# MVAU
# ---------------------------------------------------------------------------
def _grid(shape, spec):
    """Random values on a fixed-point grid — the MVAU's operating domain.

    On-grid operands make every partial sum exactly representable in f32, so
    the blocked kernel and the one-shot oracle agree bit-for-bit (off-grid
    floats can flip a threshold compare by one ulp of accumulation-order
    noise, which the real datapath never sees)."""
    q = RNG.integers(spec.qmin, spec.qmax + 1, size=shape)
    return (q * spec.scale).astype(np.float32)


@pytest.mark.parametrize("m,k,n", [
    (1, 16, 8),        # vector × small (decode-like)
    (7, 33, 130),      # nothing divides the block sizes
    (128, 128, 128),   # exactly one block
    (130, 257, 129),   # just past block boundaries
])
@pytest.mark.parametrize("levels", [3, 15])
def test_mvau_float_matches_ref(m, k, n, levels):
    x = _grid((m, k), quant.FixedPointSpec(6, 5))
    w = _grid((k, n), quant.FixedPointSpec(6, 5))
    t = np.sort(_grid((n, levels), quant.FixedPointSpec(12, 8)), axis=1)
    got = ops.mvau(jnp.asarray(x), jnp.asarray(w), jnp.asarray(t),
                   out_base=-4, out_scale=0.5, out_bias=0.25, interpret=True)
    want = ref.mvau(jnp.asarray(x), jnp.asarray(w), jnp.asarray(t),
                    out_base=-4, out_scale=0.5, out_bias=0.25)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("m,k,n", [(4, 64, 32), (130, 200, 96)])
def test_mvau_int_matches_ref(m, k, n):
    """The FINN integer datapath: int8 × int8 → int32 compare-count."""
    x = RNG.integers(-128, 128, size=(m, k)).astype(np.int8)
    w = RNG.integers(-128, 128, size=(k, n)).astype(np.int8)
    t = np.sort(RNG.integers(-4000, 4000, size=(n, 15)), axis=1).astype(np.int32)
    got = ops.mvau_int(jnp.asarray(x), jnp.asarray(w), jnp.asarray(t),
                       out_base=-8, interpret=True)
    want = ref.mvau_int(jnp.asarray(x), jnp.asarray(w), jnp.asarray(t),
                        out_base=-8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m,k,n,levels", [
    (7, 36, 8, 15),      # odd M, K not a tile multiple
    (16, 130, 129, 15),  # odd N → ragged last tile in both grid axes
    (5, 64, 32, 255),    # 8-bit grid: chunked threshold loop
])
def test_mvau_int_fused_kernel_odd_shapes(m, k, n, levels):
    """The fused integer MVAU kernel (accumulate in VMEM scratch, thresholds
    applied in-register on the int32 accumulator) is bit-exact against the
    pure oracle at ragged tile shapes, and so is the f32-exact GEMM fast
    path the CPU backend serves from."""
    x = RNG.integers(0, 16, size=(m, k)).astype(np.int8)
    w = RNG.integers(-8, 8, size=(k, n)).astype(np.int8)
    t = np.sort(RNG.integers(-500, 4000, size=(n, levels)),
                axis=1).astype(np.int32)
    want = np.asarray(ref.mvau_int(jnp.asarray(x), jnp.asarray(w),
                                   jnp.asarray(t), out_base=-3))
    got = np.asarray(ops.mvau_int(jnp.asarray(x), jnp.asarray(w),
                                  jnp.asarray(t), out_base=-3,
                                  interpret=True))
    np.testing.assert_array_equal(want, got)
    fast = np.asarray(ref.mvau_int_fast(jnp.asarray(x), jnp.asarray(w),
                                        jnp.asarray(t), out_base=-3,
                                        acc_f32_exact=True))
    np.testing.assert_array_equal(want, fast)


def test_mvau_int_packed_int4_in_kernel_unpack():
    """The packed (K, N//2) int4 buffer the lowering stores is ALSO the
    compute layout: the kernel unpacks nibbles in-register and matches the
    unpacked oracle bit-for-bit."""
    m, k, n = 6, 36, 32
    x = RNG.integers(0, 16, size=(m, k)).astype(np.int8)
    w = RNG.integers(-8, 8, size=(k, n)).astype(np.int32)
    t = np.sort(RNG.integers(-500, 3000, size=(n, 15)), axis=1).astype(np.int32)
    wp = np.asarray(quant.pack_int4(jnp.asarray(w)))
    assert wp.shape == (k, n // 2)
    want = np.asarray(ref.mvau_int(jnp.asarray(x), jnp.asarray(w),
                                   jnp.asarray(t), out_base=-3))
    got = np.asarray(ops.mvau_int(jnp.asarray(x), jnp.asarray(wp),
                                  jnp.asarray(t), out_base=-3,
                                  interpret=True, w_packed=True))
    np.testing.assert_array_equal(want, got)


@pytest.mark.parametrize("attrs,levels,want", [
    ({"int8_ok": True}, 15, "fused-pallas"),
    ({"int8_ok": True, "w_packed": True}, 15, "fused-pallas"),
    ({"int8_ok": True}, 255, "fused-pallas"),
    ({"int8_ok": True, "acc_f32_exact": True}, 1023, "f32-gemm"),
    ({"int8_ok": False, "acc_f32_exact": True}, 15, "f32-gemm"),
    ({"int8_ok": False}, 15, "ref-oracle"),
])
def test_kernel_dispatch_on_chip_keeps_wide_codes_off_the_mxu(attrs, levels,
                                                               want):
    """On a TPU only int8 codes take the fused kernel (the MXU has no wider
    integer path); wider codes go to the exact GEMM or the oracle."""
    from repro.core.graph import Node

    node = Node("mvau_int", ["x", "w", "t"], ["y"], dict(attrs))
    assert ops.kernel_dispatch(node, emulated=False, n_levels=levels) == want
    assert ops.kernel_dispatch(node, emulated=True,
                               n_levels=levels) != "fused-pallas"


def test_mvau_int_kernel_takes_int8_codes_only():
    x = jnp.zeros((4, 8), jnp.int32)
    w = jnp.zeros((8, 8), jnp.int8)
    t = jnp.zeros((8, 3), jnp.int32)
    with pytest.raises(TypeError, match="int8"):
        ops.mvau_int(x, w, t, interpret=True)


def test_threshold_counts_fast_matches_dense():
    """Both fast-count strategies — the unrolled per-level loop (L < 64) and
    searchsorted (sorted L >= 64) — equal the dense compare-count."""
    for levels in (15, 128):
        t = np.sort(RNG.integers(-50, 400, size=(8, levels)),
                    axis=1).astype(np.int32)
        acc = RNG.integers(-100, 500, size=(3, 5, 8)).astype(np.int32)
        fast = np.asarray(ref.threshold_counts_fast(jnp.asarray(acc),
                                                    jnp.asarray(t)))
        dense = np.sum(acc[..., None] >= t[None, None], axis=-1)
        np.testing.assert_array_equal(fast, dense)


def test_mvau_batched_rank3():
    x = _rand((2, 5, 48))
    w = _rand((48, 24))
    t = np.sort(_rand((24, 7), -3, 3), axis=1)
    got = ops.mvau(jnp.asarray(x), jnp.asarray(w), jnp.asarray(t), interpret=True)
    want = ref.mvau(jnp.asarray(x), jnp.asarray(w), jnp.asarray(t))
    assert got.shape == (2, 5, 24)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_mvau_many_levels_chunking():
    """L=255 exercises the chunked threshold loop (8-bit activations)."""
    spec = quant.FixedPointSpec(8, 4, signed=True)
    t = quant.thresholds_for(spec)            # (255,)
    x, w = _rand((9, 40)), _rand((40, 17))
    got = ops.mvau(jnp.asarray(x), jnp.asarray(w), jnp.asarray(t),
                   out_base=spec.qmin, interpret=True)
    want = ref.mvau(jnp.asarray(x), jnp.asarray(w), jnp.asarray(t),
                    out_base=spec.qmin)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


# ---------------------------------------------------------------------------
# qmatmul (w8a16 / w4a16)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,k,n", [(1, 32, 16), (5, 130, 64), (128, 128, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_qmatmul_w8(m, k, n, dtype):
    x = jnp.asarray(_rand((m, k)), dtype)
    w = RNG.integers(-128, 128, size=(k, n)).astype(np.int8)
    s = _rand((n,), 0.001, 0.02)
    got = ops.qmatmul(x, jnp.asarray(w), jnp.asarray(s), bits=8, interpret=True)
    want = ref.qmatmul(x, jnp.asarray(w), jnp.asarray(s), bits=8)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("m,k,n", [(3, 64, 32), (130, 96, 256)])
def test_qmatmul_w4(m, k, n):
    x = jnp.asarray(_rand((m, k)))
    codes = RNG.integers(-8, 8, size=(k, n)).astype(np.int32)
    packed = quant.pack_int4(jnp.asarray(codes))
    s = _rand((n,), 0.01, 0.1)
    got = ops.qmatmul(x, packed, jnp.asarray(s), bits=4, interpret=True)
    want = ref.qmatmul(x, packed, jnp.asarray(s), bits=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


def test_qmatmul_exactness_small_codes():
    """bf16 holds ints exactly up to 256 — the int-code matmul path is exact
    for int4 codes with K small enough; verify bit-exactness vs integer math."""
    k, n = 16, 8
    x = jnp.asarray(np.eye(k, dtype=np.float32))
    codes = RNG.integers(-8, 8, size=(k, n)).astype(np.int32)
    packed = quant.pack_int4(jnp.asarray(codes))
    s = np.ones((n,), np.float32)
    got = ops.qmatmul(x, packed, jnp.asarray(s), bits=4, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), codes.astype(np.float32))


# ---------------------------------------------------------------------------
# GlobalAccPool
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(2, 8, 8, 16), (1, 32, 32, 64), (3, 5, 7, 24)])
def test_gap_float(shape):
    x = jnp.asarray(_rand(shape))
    got = ops.gap(x, interpret=True)
    want = ref.gap(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_gap_int_exact_no_division():
    """Integer inputs accumulate exactly in int32 — the paper's no-division
    datapath."""
    x = jnp.asarray(RNG.integers(-100, 100, size=(2, 16, 16, 32)), jnp.int32)
    got = ops.gap(x, interpret=True)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref.gap(x)))
