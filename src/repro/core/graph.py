"""A small FINN-like dataflow-graph IR + JAX interpreter.

The paper's contribution lives at the *graph-transformation* level: FINN takes
an ONNX graph and applies architecture-dependent "Streamline" and
"Convert-to-HW-Layer" passes until every node maps onto a hardware unit
(MVAU, pooling, thresholding).  We reproduce that level faithfully with our
own minimal IR so the passes in :mod:`repro.core.transforms` are real graph
rewrites with checkable semantics, not metaphors.

Ops (all the paper's ResNet-9 needs, plus the fused HW ops):

=================  ==========================================================
``im2col``         patch extraction (the FINN lowering of Conv)
``matmul``         A @ W (+ bias); weights are graph initializers
``multithreshold`` FINN activation quantization: ``base + Σ 1[x ≥ Tᵢ]``
``transpose``      explicit layout permutation (NCHW↔NHWC)
``reduce_mean``    spatial mean — *not* HW-mappable; must be streamlined away
``global_acc_pool``FINN's GlobalAccPool: integer spatial **sum** (no divide)
``mul`` / ``add``  scalar/elementwise affine (scales get folded by passes)
``maxpool``        2×2 window max
``mvau``           fused matmul+multithreshold — executed by the Pallas kernel
``layernorm``      integer LayerNorm with gain and bias (ViT, I-BERT's)
``igelu``          integer GELU (ViT, I-BERT's)
``vit_tokens``     class token + patch embeddings + learned positions
``attn_full``      bidirectional integer attention over packed q|k|v codes
``cls_readout``    the class token of each sequence
=================  ==========================================================

Tensors flow in a named environment; layouts are tracked as node attrs so the
transpose-absorption pass can reason about NCHW/NHWC explicitly (paper
Sec. III-C).

Graph-query complexity: ``producer``/``consumers`` are backed by a lazily
built index (one O(V+E) sweep) that mutating passes drop via
:meth:`Graph.invalidate` — without it every streamline pass iteration paid an
O(n²) rescan (measured in ``benchmarks/compile_bench.py``).  ``toposort`` is
Kahn's algorithm on the same adjacency information.
"""

from __future__ import annotations

import collections
import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Node", "Graph", "execute", "GraphBuildError", "set_index_enabled"]


class GraphBuildError(RuntimeError):
    """A graph reached the HW-mapping stage with non-mappable nodes."""


# Escape hatch for benchmarking the cached index against the old linear
# scans (benchmarks/compile_bench.py flips this) — not for production use.
_INDEX_ENABLED = True


def set_index_enabled(enabled: bool) -> None:
    global _INDEX_ENABLED
    _INDEX_ENABLED = bool(enabled)


@dataclasses.dataclass
class Node:
    op: str
    inputs: List[str]
    outputs: List[str]
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def copy(self) -> "Node":
        return Node(self.op, list(self.inputs), list(self.outputs), dict(self.attrs))


@dataclasses.dataclass
class Graph:
    nodes: List[Node]
    inputs: List[str]
    outputs: List[str]
    initializers: Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)
    name: str = "graph"
    # Verified structural properties (tokens such as
    # "trailing_axis_thresholds") — maintained by the PassManager, advisory
    # for humans; precondition checks always re-derive from structure.
    properties: Set[str] = dataclasses.field(default_factory=set)
    # Optional tensor-shape annotations, filled by infer_shapes().
    shapes: Dict[str, Tuple[int, ...]] = dataclasses.field(default_factory=dict)
    # Per-tensor fixed-point datatype annotations (FixedPointSpec or None for
    # float tensors), keyed by tensor name.  Seeded by exporters (graph
    # inputs / weight initializers), propagated to every tensor by the
    # ``infer_datatypes`` pass (core/datatypes.py).  The structured mutators
    # below keep the map coherent under rewiring; like ``shapes`` it is an
    # annotation — passes that need it re-derive via infer_datatypes.
    dtypes: Dict[str, Any] = dataclasses.field(default_factory=dict)
    _cache: Optional[Dict[str, Any]] = dataclasses.field(
        default=None, init=False, repr=False, compare=False)

    def copy(self) -> "Graph":
        g = Graph([n.copy() for n in self.nodes], list(self.inputs),
                  list(self.outputs), dict(self.initializers), self.name,
                  set(self.properties), dict(self.shapes), dict(self.dtypes))
        return g

    # -- cached adjacency index --------------------------------------------
    def invalidate(self) -> None:
        """Drop the producer/consumer index.  Call after mutating node
        wiring *directly*; the structured mutators below (``set_input``,
        ``remove_node``, ``insert_node``, ...) maintain the index
        incrementally and do NOT require it."""
        self._cache = None

    # -- structured mutators (keep the adjacency index valid in O(1)) -------
    def set_input(self, node: Node, pos: int, tensor: str) -> None:
        old = node.inputs[pos]
        node.inputs[pos] = tensor
        c = self._cache
        if c is not None and old != tensor:
            lst = c["cons"].get(old)
            if lst and node in lst:
                lst.remove(node)            # one occurrence per position
            c["cons"].setdefault(tensor, []).append(node)
            c["names"].add(tensor)

    def set_output(self, node: Node, pos: int, tensor: str) -> None:
        old = node.outputs[pos]
        node.outputs[pos] = tensor
        if old != tensor and old in self.dtypes and tensor not in self.dtypes:
            # the renamed tensor carries the same values — the annotation
            # follows it (the old name usually gets re-produced by a
            # value-preserving node the caller inserts next)
            self.dtypes[tensor] = self.dtypes[old]
        c = self._cache
        if c is not None and old != tensor:
            if c["prod"].get(old) is node:
                del c["prod"][old]
            c["prod"][tensor] = node
            c["names"].add(tensor)

    def remove_node(self, node: Node) -> None:
        self.nodes.remove(node)
        c = self._cache
        if c is not None:
            for t in node.outputs:
                if c["prod"].get(t) is node:
                    del c["prod"][t]
            for t in node.inputs:
                lst = c["cons"].get(t)
                if lst and node in lst:
                    lst.remove(node)
        for t in node.outputs:
            if self.producer(t) is None and t not in self.initializers \
                    and t not in self.inputs:
                self.dtypes.pop(t, None)    # tensor ceased to exist

    def insert_node(self, pos: int, node: Node) -> None:
        self.nodes.insert(pos, node)
        c = self._cache
        if c is not None:
            for t in node.outputs:
                c["prod"][t] = node
                c["names"].add(t)
            for t in node.inputs:
                c["cons"].setdefault(t, []).append(node)
                c["names"].add(t)

    def insert_after(self, ref: Node, node: Node) -> None:
        self.insert_node(self.nodes.index(ref) + 1, node)

    def _index(self) -> Optional[Dict[str, Any]]:
        if not _INDEX_ENABLED:
            return None
        if self._cache is None:
            prod: Dict[str, Node] = {}
            cons: Dict[str, List[Node]] = {}
            names: Set[str] = set(self.initializers)
            for n in self.nodes:
                for t in n.outputs:
                    prod[t] = n
                    names.add(t)
                for t in n.inputs:
                    cons.setdefault(t, []).append(n)
                    names.add(t)
            self._cache = {"prod": prod, "cons": cons, "names": names}
        return self._cache

    # -- small query helpers used by the transform passes -------------------
    def producer(self, tensor: str) -> Optional[Node]:
        idx = self._index()
        if idx is not None:
            return idx["prod"].get(tensor)
        for n in self.nodes:
            if tensor in n.outputs:
                return n
        return None

    def consumers(self, tensor: str) -> List[Node]:
        idx = self._index()
        if idx is not None:
            # the index stores one entry per consuming *position* (so the
            # mutators can retire occurrences one at a time); de-dup here so
            # a node reading the same tensor twice is reported once, exactly
            # like the linear scan
            seen, out = set(), []
            for n in idx["cons"].get(tensor, ()):
                if id(n) not in seen:
                    seen.add(id(n))
                    out.append(n)
            return out
        return [n for n in self.nodes if tensor in n.inputs]

    def fresh_name(self, stem: str) -> str:
        idx = self._index()
        if idx is not None:
            taken = idx["names"]
        else:
            taken = set(self.initializers)
            for n in self.nodes:
                taken.update(n.inputs)
                taken.update(n.outputs)
        i = 0
        while f"{stem}_{i}" in taken:
            i += 1
        return f"{stem}_{i}"

    def toposort(self) -> None:
        """Re-order ``nodes`` topologically (Kahn's algorithm, O(V+E))."""
        avail = set(self.inputs) | set(self.initializers)
        indeg: Dict[int, int] = {}
        waiting: Dict[str, List[Node]] = {}
        ready: collections.deque = collections.deque()
        for n in self.nodes:
            d = 0
            for i in n.inputs:
                if i not in avail:
                    d += 1
                    waiting.setdefault(i, []).append(n)
            indeg[id(n)] = d
            if d == 0:
                ready.append(n)
        ordered: List[Node] = []
        while ready:
            n = ready.popleft()
            ordered.append(n)
            for t in n.outputs:
                if t in avail:
                    continue
                avail.add(t)
                for c in waiting.get(t, ()):
                    indeg[id(c)] -= 1
                    if indeg[id(c)] == 0:
                        ready.append(c)
        if len(ordered) != len(self.nodes):
            missing = {i for n in self.nodes if indeg[id(n)] > 0
                       for i in n.inputs if i not in avail}
            raise GraphBuildError(f"graph has unsatisfiable inputs: {missing}")
        self.nodes = ordered
        self.invalidate()

    # -- pass-manager integration -------------------------------------------
    def transform(self, pass_like, **kwargs) -> "Graph":
        """Apply one registered pass (by name, GraphPass, or raw callable),
        with its preconditions checked.  Returns the rewritten graph."""
        from repro.core.passes import apply_pass

        return apply_pass(self, pass_like, **kwargs)

    def infer_shapes(self, feeds: Dict[str, Any]) -> "Graph":
        """Annotate ``self.shapes`` for every tensor by abstract evaluation
        (no FLOPs — ``jax.eval_shape`` over the interpreter).  ``feeds`` maps
        graph inputs to arrays or ShapeDtypeStructs."""
        shapes: Dict[str, Tuple[int, ...]] = {}

        def run(feed_structs):
            env = {k: jnp.zeros(v.shape, v.dtype)
                   for k, v in self.initializers.items()}
            env.update(feed_structs)
            for node in self.nodes:
                fn = _EXECUTORS.get(node.op)
                if fn is None:
                    raise GraphBuildError(f"no executor for op '{node.op}'")
                out = fn(node, *[env[i] for i in node.inputs])
                outs = out if isinstance(out, (tuple, list)) else (out,)
                for nm, val in zip(node.outputs, outs):
                    env[nm] = val
            return env

        structs = {k: jax.ShapeDtypeStruct(np.shape(v) or getattr(v, "shape", ()),
                                           getattr(v, "dtype", jnp.float32))
                   for k, v in feeds.items()}
        env = jax.eval_shape(run, structs)
        for nm, sds in env.items():
            shapes[nm] = tuple(sds.shape)
        self.shapes = shapes
        return self


# ---------------------------------------------------------------------------
# Interpreter
# ---------------------------------------------------------------------------
def im2col(x: jax.Array, k: int = 3, stride: int = 1,
           pad: int = 1) -> jax.Array:
    """NHWC patch extraction -> (N, OH, OW, KH*KW*C), columns in (kh, kw, c)
    order. FINN's Conv lowering; the QAT model's convs call it too.

    The k·k windows are static strided ``lax.slice``s of the padded input,
    joined on the channel axis. Index-array gathers (and jnp's strided
    ``x[a:b:s]``, which lowers to a gather) become ``while`` loops of dynamic
    slices on the TPU; slices and a concatenate stay plain fusions.

    Windows that tile the input (``k == stride``, no padding: a ViT's patch
    embedding) are a reshape and a transpose instead of k·k slices."""
    n, h, w, c = x.shape
    oh = (h + 2 * pad - k) // stride + 1
    ow = (w + 2 * pad - k) // stride + 1
    if k == stride and pad == 0:
        x = jax.lax.slice(x, (0, 0, 0, 0), (n, oh * k, ow * k, c))
        x = x.reshape(n, oh, k, ow, k, c).transpose(0, 1, 3, 2, 4, 5)
        return x.reshape(n, oh, ow, k * k * c)
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    return jnp.concatenate(
        [jax.lax.slice(xp, (0, i, j, 0),
                       (n, i + (oh - 1) * stride + 1,
                        j + (ow - 1) * stride + 1, c),
                       (1, stride, stride, 1))
         for i in range(k) for j in range(k)], axis=-1)


def _ex_im2col(node: Node, x: jax.Array) -> jax.Array:
    return im2col(x, node.attrs["kernel"], node.attrs["stride"],
                  node.attrs["pad"])


def _ex_matmul(node: Node, x: jax.Array, w: jax.Array,
               b: Optional[jax.Array] = None) -> jax.Array:
    # full f32 products: the grid emulation is exact only if operands are
    # not rounded to bf16, which a TPU's default precision does
    y = jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)
    if b is not None:
        y = y + b
    return y


def _ex_multithreshold(node: Node, x: jax.Array, t: jax.Array) -> jax.Array:
    from repro.core import quant

    axis = node.attrs.get("channel_axis", -1)
    if t.ndim == 2 and axis not in (-1, x.ndim - 1):
        # Per-channel thresholds on a non-trailing axis: legal in the IR (this
        # is exactly the NCHW case the paper's pass removes) but slow — move
        # channels last, threshold, move back.
        xt = jnp.moveaxis(x, axis, -1)
        y = quant.multithreshold(xt, t, node.attrs.get("out_base", 0),
                                 node.attrs.get("out_scale", 1.0),
                                 node.attrs.get("out_bias", 0.0))
        return jnp.moveaxis(y, -1, axis)
    return quant.multithreshold(x, t, node.attrs.get("out_base", 0),
                                node.attrs.get("out_scale", 1.0),
                                node.attrs.get("out_bias", 0.0))


def _ex_mvau(node: Node, x: jax.Array, w: jax.Array, t: jax.Array) -> jax.Array:
    """Fused matmul+threshold — dispatched to the Pallas MVAU kernel,
    compiled on a TPU and interpreted elsewhere."""
    from repro.kernels import ops as kops

    return kops.mvau(
        x, w, t,
        out_base=node.attrs.get("out_base", 0),
        out_scale=node.attrs.get("out_scale", 1.0),
        out_bias=node.attrs.get("out_bias", 0.0),
    )


# -- integer-datapath ops (emitted by core.datatypes.LowerToIntegerDatapath) --
def _ex_quantize(node: Node, x: jax.Array) -> jax.Array:
    """Real → integer codes at the node's annotated spec (int32 codes —
    narrow storage is an initializer concern; activations stay registers)."""
    from repro.core import quant

    spec = quant.FixedPointSpec(node.attrs["bits"], node.attrs["frac_bits"],
                                node.attrs.get("signed", True))
    return quant.quantize(x, spec)


def _ex_dequantize(node: Node, q: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * jnp.float32(node.attrs["scale"])


def _ex_mvau_int(node: Node, x: jax.Array, w: jax.Array,
                 t: jax.Array) -> jax.Array:
    """Integer MVAU: code × code matmul, int32 accumulate, int thresholds."""
    from repro.core import quant
    from repro.kernels import ref

    if node.attrs.get("w_packed"):
        w = quant.unpack_int4(w)
    return ref.mvau_int(x, w, t, out_base=node.attrs.get("out_base", 0))


def _ex_matmul_int(node: Node, x: jax.Array, w: jax.Array) -> jax.Array:
    """Bare integer-code matmul (int32 accumulate) — the pre-fusion form."""
    from repro.core import quant
    from repro.kernels import ref

    if node.attrs.get("w_packed"):
        w = quant.unpack_int4(w)
    return ref.matmul_int(x, w)


def _ex_multithreshold_int(node: Node, x: jax.Array,
                           t: jax.Array) -> jax.Array:
    from repro.kernels import ref

    return ref.multithreshold_int(x, t, out_base=node.attrs.get("out_base", 0))


def _ex_requantize(node: Node, q: jax.Array) -> jax.Array:
    """Exact integer regrid (shift + round-half-even + clip) — the fused
    form of an interior dequantize→quantize pair."""
    from repro.kernels import ref

    return ref.requantize(q, node.attrs["shift"], node.attrs["bits"],
                          node.attrs["frac_bits"],
                          node.attrs.get("signed", True))


def _ex_gap(node: Node, x: jax.Array) -> jax.Array:
    if jnp.issubdtype(x.dtype, jnp.integer):
        x = x.astype(jnp.int32)     # sub-int32 codes must not wrap in the sum
    return jnp.sum(x, axis=tuple(node.attrs["axes"]))


# -- decode-workload ops (PR 10: models.lm export; see DESIGN.md §14) --------
def _ex_embed(node: Node, table: jax.Array, ids: jax.Array) -> jax.Array:
    """Token-id row gather.  After integer lowering the table holds codes
    (packed int4 when ``w_packed``); gathering codes then dequantizing is
    bit-for-bit the float gather — rows are untouched values either way."""
    out = jnp.take(table, ids.astype(jnp.int32), axis=0)
    if node.attrs.get("w_packed"):
        from repro.core import quant

        out = quant.unpack_int4(out)
    return out


def _ex_rmsnorm(node: Node, x: jax.Array, g: jax.Array) -> jax.Array:
    # mirrors models.layers.rmsnorm exactly (f32 internal math) — the
    # decode_step_ref ⇔ compiled-graph bitwise contract depends on it
    eps = node.attrs.get("eps", 1e-6)
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * g).astype(x.dtype)


def _ex_attn_decode(node: Node, q, k_new, v_new, k_cache, v_cache, pos):
    from repro.kernels import ref

    return ref.attn_decode(q, k_new, v_new, k_cache, v_cache,
                           pos.astype(jnp.int32), node.attrs["heads"])


def _ex_attn_prefill(node: Node, q, k, v):
    from repro.kernels import ref

    return ref.attn_prefill(q, k, v, node.attrs["heads"])


# -- vision-transformer ops (models.vit export) -------------------------------
# LayerNorm and GELU in integer arithmetic, as in I-BERT (arXiv 2101.01321):
# the float input is put on a fixed-point grid (one exact rounding) and every
# later step is int32, so the output has the same bits in any program, however
# the compiler fuses it or orders a sum.  The QAT model calls these too.
LN_IN_BITS = 23      # LayerNorm input codes saturate to +-2**23
LN_SQ_BITS = 11      # deviations scaled to |e| <= 2**11 before squaring
LN_GAIN_FRAC = 10    # gains on the grid 2**-10, saturated to +-2**12 codes
LN_SIGMA_FRAC = 4    # the standard deviation with 4 fraction bits
GELU_IN_BITS = 15    # GELU input codes saturate to +-2**15
GELU_PHI_FRAC = 10   # the normal CDF on the grid 2**-10
IGELU_A, IGELU_B = 0.2888, 1.769    # erf(u) ~ 1 - A (min(u, B) - B)^2, u >= 0


def _grid_codes(x: jax.Array, frac: int, bits: int) -> jax.Array:
    """``x`` on the grid ``2**-frac``, round half to even, saturated to
    ``[-2**bits, 2**bits - 1]``: int32 codes."""
    q = jnp.round(x.astype(jnp.float32) * np.float32(2.0 ** frac))
    return jnp.clip(q, -(2 ** bits), 2 ** bits - 1).astype(jnp.int32)


def isqrt(v: jax.Array) -> jax.Array:
    """``floor(sqrt(v))`` of int32 ``v`` in ``[1, 2**31)``: Newton's step
    from above, which falls to the answer in at most 16 steps and stays."""
    x = jnp.full_like(v, 46341)             # ceil(sqrt(2**31))
    for _ in range(20):
        x = jnp.minimum(x, (x + v // x) >> 1)
    return x


def layernorm(x: jax.Array, g: jax.Array, b: jax.Array, eps: float,
              in_frac: int, out_frac: int) -> jax.Array:
    """Integer LayerNorm over the trailing axis (at most 511 wide), with gain
    and bias.  ``x`` is rounded onto the grid ``2**-in_frac``; its codes less
    their rounded mean are scaled by a power of two to at most ``2**11``
    (``e``), whose mean square, plus ``eps``, gives the standard deviation
    ``sigma`` with 4 fraction bits (integer square root).  The output is
    ``e / sigma`` times the gain (on ``2**-10``), rounded half up onto the
    grid ``2**-out_frac``, plus the bias on that grid: float32, exactly on
    the grid."""
    n = x.shape[-1]
    if n >= 512 or out_frac > LN_GAIN_FRAC + 10:
        raise ValueError(f"no int32 LayerNorm over {n} at {out_frac} bits")
    c = _grid_codes(x, in_frac, LN_IN_BITS)
    d = c - (jnp.sum(c, axis=-1, keepdims=True) + n // 2) // n
    m = jnp.max(jnp.abs(d), axis=-1, keepdims=True)
    k = sum((m >= 2 ** j).astype(jnp.int32)
            for j in range(LN_SQ_BITS, LN_IN_BITS + 2))
    e = (d + ((1 << k) >> 1)) >> k
    var = jnp.sum(e * e, axis=-1, keepdims=True) // n
    eps_q = round(eps * 2.0 ** (2 * (in_frac + LN_SIGMA_FRAC)))
    sigma = isqrt((var << 2 * LN_SIGMA_FRAC)
                  + jnp.maximum(eps_q >> 2 * k, 1))
    gq = _grid_codes(g, LN_GAIN_FRAC, 12)
    num = e * gq
    # floor(num * 2**sh / sigma), by long division where sh > 0; half up
    sh = LN_SIGMA_FRAC + out_frac - LN_GAIN_FRAC + 1
    if sh > 0:
        q0 = num // sigma
        t = q0 * (2 ** sh) + ((num - q0 * sigma) << sh) // sigma
    else:
        t = num // (sigma * 2 ** -sh)
    y = ((t + 1) >> 1) + _grid_codes(b, out_frac, 20)
    return y.astype(jnp.float32) * np.float32(2.0 ** -out_frac)


def igelu(x: jax.Array, in_frac: int, out_frac: int) -> jax.Array:
    """I-BERT's integer GELU: ``x Phi(x)`` with ``Phi(x) = (1 + erf(x /
    sqrt 2)) / 2`` and erf's second-order fit, on int32 codes of ``x`` on
    the grid ``2**-in_frac`` (at most 9 bits); ``Phi`` on ``2**-10``; the
    product rounded half up onto the grid ``2**-out_frac``, as float32."""
    if not 0 <= in_frac <= 9 or not 0 <= out_frac < in_frac + GELU_PHI_FRAC:
        raise ValueError(f"no int32 GELU from {in_frac} to {out_frac} bits")
    q = _grid_codes(x, in_frac, GELU_IN_BITS)
    qb = round(IGELU_B * math.sqrt(2.0) * 2 ** in_frac)
    t = jnp.minimum(jnp.abs(q), qb) - qb
    sh = 2 * in_frac + 14 - GELU_PHI_FRAC
    tail = (t * t * round(IGELU_A * 2 ** 12) + (1 << (sh - 1))) >> sh
    phi = jnp.where(q > 0, 2 ** GELU_PHI_FRAC - tail, tail)
    sh = in_frac + GELU_PHI_FRAC - out_frac
    y = (q * phi + (1 << (sh - 1))) >> sh
    return y.astype(jnp.float32) * np.float32(2.0 ** -out_frac)


def vit_tokens(x: jax.Array, cls: jax.Array, pos: jax.Array) -> jax.Array:
    """(N, GH, GW, D) patch embeddings -> (N, 1 + GH·GW, D) tokens: the
    class token first, then the patches in row-major order, plus the
    learned positions (1 + GH·GW, D)."""
    n, d = x.shape[0], x.shape[-1]
    patches = x.reshape(n, -1, d)
    cls = jnp.broadcast_to(cls.astype(patches.dtype), (n, 1, d))
    return jnp.concatenate([cls, patches], axis=1) + pos


def _ex_attn_full(node: Node, qkv):
    from repro.kernels import ref

    return ref.attn_full(qkv, node.attrs["heads"], node.attrs["frac_bits"])


_EXECUTORS: Dict[str, Callable[..., jax.Array]] = {
    "im2col": _ex_im2col,
    "matmul": _ex_matmul,
    "multithreshold": _ex_multithreshold,
    "mvau": _ex_mvau,
    "mvau_int": _ex_mvau_int,
    "matmul_int": _ex_matmul_int,
    "multithreshold_int": _ex_multithreshold_int,
    "requantize": _ex_requantize,
    "quantize": _ex_quantize,
    "dequantize": _ex_dequantize,
    "transpose": lambda node, x: jnp.transpose(x, node.attrs["perm"]),
    "reduce_mean": lambda node, x: jnp.mean(x, axis=tuple(node.attrs["axes"])),
    "global_acc_pool": _ex_gap,
    "mul": lambda node, x, c=None: x * (node.attrs["value"] if c is None else c),
    "add": lambda node, a, b=None: a + (node.attrs["value"] if b is None else b),
    "maxpool": lambda node, x: _maxpool(node, x),
    "relu": lambda node, x: jnp.maximum(x, 0),
    "flatten": lambda node, x: x.reshape(x.shape[0], -1),
    "embed": _ex_embed,
    "rmsnorm": _ex_rmsnorm,
    "silu": lambda node, x: jax.nn.silu(x),
    "gelu": lambda node, x: jax.nn.gelu(x),
    "attn_decode": _ex_attn_decode,
    "attn_prefill": _ex_attn_prefill,
    "layernorm": lambda node, x, g, b: layernorm(
        x, g, b, node.attrs["eps"], node.attrs["in_frac"],
        node.attrs["out_frac"]),
    "igelu": lambda node, x: igelu(x, node.attrs["in_frac"],
                                   node.attrs["out_frac"]),
    "vit_tokens": lambda node, x, cls, pos: vit_tokens(x, cls, pos),
    "cls_readout": lambda node, x: x[:, 0],
    "attn_full": _ex_attn_full,
}


def _maxpool(node: Node, x: jax.Array) -> jax.Array:
    k = node.attrs.get("kernel", 2)
    n, h, w, c = x.shape
    x = x[:, : h - h % k, : w - w % k, :]
    x = x.reshape(n, h // k, k, w // k, k, c)
    return x.max(axis=(2, 4))


def execute(graph: Graph, feeds: Dict[str, jax.Array]) -> List[jax.Array]:
    """Run the graph; returns the output tensors in ``graph.outputs`` order.

    This is the per-node *interpreter*: each op dispatches eagerly, which is
    perfect for debugging passes (inspect any intermediate tensor by name)
    and exactly what :class:`repro.core.deploy.DeployedModel` replaces on the
    serving hot path with a single jitted program.
    """
    env: Dict[str, jax.Array] = {k: jnp.asarray(v) for k, v in graph.initializers.items()}
    env.update({k: jnp.asarray(v) for k, v in feeds.items()})
    for node in graph.nodes:
        fn = _EXECUTORS.get(node.op)
        if fn is None:
            raise GraphBuildError(f"no executor for op '{node.op}'")
        args = [env[i] for i in node.inputs]
        out = fn(node, *args)
        outs = out if isinstance(out, (tuple, list)) else (out,)
        for name, val in zip(node.outputs, outs):
            env[name] = val
    return [env[o] for o in graph.outputs]
