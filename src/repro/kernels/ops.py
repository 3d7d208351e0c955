"""Public jit'd wrappers around the Pallas kernels.

These normalize ranks (leading batch dims flatten into M), broadcast
per-tensor thresholds to the per-channel (N, L) form the kernels expect, and
pick ``interpret=True`` automatically off-TPU so the same call sites run in
CI (CPU) and production (TPU).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.quant import FixedPointSpec
from repro.kernels import ref
from repro.kernels.attention import attn_full_pallas
from repro.kernels.gap import gap_pallas
from repro.kernels.mvau import mvau_int_pallas, mvau_pallas
from repro.kernels.qmatmul import qmatmul_pallas

__all__ = ["mvau", "mvau_int", "qmatmul", "gap", "attn_full",
           "default_interpret", "graph_op_impls", "kernel_dispatch",
           "attn_trace_count"]


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _as_2d(x: jax.Array):
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


def _thresholds_2d(t: jax.Array, n: int) -> jax.Array:
    if t.ndim == 1:
        return jnp.broadcast_to(t[None, :], (n, t.shape[0]))
    return t


def mvau(x: jax.Array, w: jax.Array, thresholds: jax.Array,
         out_base: float = 0.0, out_scale: float = 1.0, out_bias: float = 0.0,
         interpret: Optional[bool] = None) -> jax.Array:
    """Fused ``multithreshold(x @ w)`` — float/QAT-grid datapath."""
    interpret = default_interpret() if interpret is None else interpret
    x2, lead = _as_2d(x)
    t2 = _thresholds_2d(jnp.asarray(thresholds, jnp.float32), w.shape[1])
    y = mvau_pallas(x2.astype(jnp.float32), w.astype(jnp.float32), t2,
                    out_base=float(out_base), out_scale=float(out_scale),
                    out_bias=float(out_bias), interpret=interpret)
    return y.reshape(*lead, w.shape[1])


def mvau_int(x_codes: jax.Array, w_codes: jax.Array, thresholds_int: jax.Array,
             out_base: int = 0, interpret: Optional[bool] = None,
             w_packed: bool = False) -> jax.Array:
    """Integer MVAU on the int8 MXU: int8 codes in, int32 codes out (FINN
    path).

    ``w_packed`` feeds the (K, N//2) packed-int4 buffer straight to the
    kernel, which unpacks nibbles to int8 in VMEM — the packed form the
    lowering stores is also the compute form.
    """
    interpret = default_interpret() if interpret is None else interpret
    x2, lead = _as_2d(x_codes)
    n = w_codes.shape[1] * (2 if w_packed else 1)
    t2 = _thresholds_2d(jnp.asarray(thresholds_int, jnp.int32), n)
    y = mvau_int_pallas(x2, w_codes, t2, out_base=int(out_base),
                        w_packed=w_packed, interpret=interpret)
    return y.reshape(*lead, n)


def qmatmul(x: jax.Array, w_codes: jax.Array, scale: jax.Array, bits: int = 8,
            interpret: Optional[bool] = None) -> jax.Array:
    """Weight-only quantized matmul (w8a16 / w4a16 serving path)."""
    interpret = default_interpret() if interpret is None else interpret
    x2, lead = _as_2d(x)
    n = w_codes.shape[1] * (2 if bits == 4 else 1)
    y = qmatmul_pallas(x2, w_codes, scale, bits=bits, interpret=interpret)
    return y.reshape(*lead, n)


def gap(x: jax.Array, interpret: Optional[bool] = None) -> jax.Array:
    """GlobalAccPool spatial sum (N, H, W, C) -> (N, C)."""
    interpret = default_interpret() if interpret is None else interpret
    return gap_pallas(x, interpret=interpret)


def attn_full(qkv: jax.Array, heads: int, frac_bits: int,
              interpret: Optional[bool] = None) -> jax.Array:
    """Bidirectional multi-head attention over packed q|k|v (B, S, 3D) on
    the activation grid of ``frac_bits`` fraction bits."""
    interpret = default_interpret() if interpret is None else interpret
    return attn_full_pallas(qkv, heads=heads, frac_bits=frac_bits,
                            interpret=interpret)


_attn_traces = [0]     # deployed attention nodes traced; see attn_trace_count


def attn_trace_count() -> int:
    """How often this process traced a deployed ``attn_full`` node: once
    per attention layer of every program compiled (the Pallas kernel on a
    TPU, its oracle when emulated).  Flat under load means no retrace."""
    return _attn_traces[0]


# ---------------------------------------------------------------------------
# Graph-node lowering (core.deploy dispatches HW ops onto these kernels)
# ---------------------------------------------------------------------------
_PALLAS_MAX_LEVELS = 512  # beyond this the chunked in-kernel count loses to
                          # the XLA searchsorted path on sorted tables


def kernel_dispatch(node, emulated: bool,
                    n_levels: Optional[int] = None) -> str:
    """Which datapath a graph node executes on — the single decision point.

    Both the deploy-time executors below and ``DeployedModel.report()``'s
    per-node dispatch table call this, so what the report claims is by
    construction what actually runs.  Labels:

    * ``fused-pallas`` — compiled fused integer MVAU (int8 MXU / packed-int4
      unpack in VMEM, thresholds applied on the accumulator in VMEM); only
      for codes that fit int8 (``int8_ok``) — the MXU has no wider path;
    * ``int8-dot``   — XLA ``dot_general`` at int8 with int32 accumulation;
    * ``f32-gemm``   — exact integer compute through the backend's f32 GEMM
      at full precision (proof obligation ``acc_f32_exact`` discharged at
      lowering time);
    * ``ref-oracle`` — naive exact integer fallback;
    * ``pallas``     — compiled float Pallas kernel;
    * ``fast-count`` / ``int-shift`` — vectorized integer threshold count /
      requantize shift (same code on every backend);
    * ``xla``        — plain XLA lowering (data movement, add, ...).
    """
    op = node.op
    if op == "mvau_int":
        if not emulated and node.attrs.get("int8_ok") and (
                n_levels is None or n_levels <= _PALLAS_MAX_LEVELS):
            return "fused-pallas"
        if node.attrs.get("acc_f32_exact"):
            return "f32-gemm"
        return "ref-oracle"
    if op == "matmul_int":
        if not emulated and node.attrs.get("int8_ok"):
            return "int8-dot"
        if node.attrs.get("acc_f32_exact"):
            return "f32-gemm"
        return "ref-oracle"
    if op == "multithreshold_int":
        return "fast-count"
    if op == "requantize":
        return "int-shift"
    if op in ("mvau", "global_acc_pool", "attn_full"):
        return "ref-oracle" if emulated else "pallas"
    return "xla"


def graph_op_impls(interpret: Optional[bool] = None):
    """Executors for the HW graph ops, keyed by op name.

    ``core.deploy`` overlays these on the interpreter's executor table when
    lowering a streamlined graph to the single jitted ``DeployedModel``
    callable, so the backend decision is made once per compile (not re-read
    from node attrs on every call).  On TPU the Pallas MVAU/GAP/attention
    kernels dispatch compiled; off-TPU — where Pallas only *emulates* via
    interpret mode — nodes lower to the XLA-native oracles from :mod:`ref`
    instead.  The MVAU/GAP paths are bit-identical on the fixed-point grid
    (every operand and partial sum is exactly representable; asserted
    kernel-vs-oracle in tests/test_kernels.py and compiled-vs-interpreter in
    tests/test_compile.py), and so is the integer attention
    (tests/test_vit.py; ``repro.compile``'s golden check).
    """
    emulated = default_interpret() if interpret is None else interpret

    def _mvau_node(node, x, w, t):
        kw = dict(out_base=node.attrs.get("out_base", 0),
                  out_scale=node.attrs.get("out_scale", 1.0),
                  out_bias=node.attrs.get("out_bias", 0.0))
        if emulated:
            return ref.mvau(x.astype(jnp.float32), w, jnp.asarray(t), **kw)
        return mvau(x, w, t, interpret=False, **kw)

    def _mvau_int_node(node, x, w, t):
        from repro.core import quant as Q

        base = node.attrs.get("out_base", 0)
        disp = kernel_dispatch(node, emulated, n_levels=t.shape[-1])
        if disp == "fused-pallas":
            return mvau_int(x.astype(jnp.int8), w.astype(jnp.int8), t,
                            out_base=base, interpret=False,
                            w_packed=bool(node.attrs.get("w_packed")))
        if node.attrs.get("w_packed"):
            w = Q.unpack_int4(w)
        # exact fast path through the f32 GEMM when lowering proved the
        # window, else exact int32 fallback — both bit-identical to the
        # oracle, both with the fast threshold count
        return ref.mvau_int_fast(
            x, w, t, out_base=base,
            acc_f32_exact=disp == "f32-gemm")

    def _matmul_int_node(node, x, w):
        from repro.core import quant as Q

        disp = kernel_dispatch(node, emulated)
        if node.attrs.get("w_packed"):
            w = Q.unpack_int4(w)
        if disp == "int8-dot":
            return jax.lax.dot_general(
                x.astype(jnp.int8), w.astype(jnp.int8),
                (((x.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
        return ref.matmul_int_fast(x, w,
                                   acc_f32_exact=disp == "f32-gemm")

    def _multithreshold_int_node(node, x, t):
        base = node.attrs.get("out_base", 0)
        counts = ref.threshold_counts_fast(x.astype(jnp.int32), t)
        return (base + counts).astype(jnp.int32)

    def _requantize_node(node, q):
        return ref.requantize(q, node.attrs["shift"], node.attrs["bits"],
                              node.attrs["frac_bits"],
                              node.attrs.get("signed", True))

    def _gap_node(node, x):
        axes = tuple(node.attrs["axes"])
        if x.ndim == 4 and axes == (1, 2):
            return ref.gap(x) if emulated else gap(x, interpret=False)
        if jnp.issubdtype(x.dtype, jnp.integer):
            x = x.astype(jnp.int32)
        return jnp.sum(x, axis=axes)

    def _attn_full_node(node, qkv):
        _attn_traces[0] += 1            # trace time only: the body is jitted
        heads, frac = node.attrs["heads"], node.attrs["frac_bits"]
        if emulated:
            return ref.attn_full(qkv, heads, frac)
        return attn_full(qkv, heads, frac, interpret=False)

    return {"mvau": _mvau_node, "mvau_int": _mvau_int_node,
            "matmul_int": _matmul_int_node,
            "multithreshold_int": _multithreshold_int_node,
            "requantize": _requantize_node,
            "global_acc_pool": _gap_node,
            "attn_full": _attn_full_node}
