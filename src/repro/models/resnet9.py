"""ResNet-9 — the paper's few-shot backbone (PEFSL / EASY), quantization-aware.

Two execution forms, numerically identical by construction:

1. **QAT model** (this module's ``forward``): im2col+matmul convolutions with
   fake-quantized weights, per-channel BN affine, ReLU, activation
   fake-quant — trainable end-to-end on the exact deployment grid.
2. **Exported dataflow graph** (``export_graph``): the FINN/ONNX view of the
   same network — MatMul nodes with quantized weight initializers, BN+ReLU+
   act-quant folded into per-channel **MultiThreshold** nodes, the stray
   NHWC→NCHW transposes the PyTorch export would insert (paper Fig. 4), and
   the final spatial ``reduce_mean``.  Running RESNET9_BUILD_STEPS on it
   yields the HW graph (MVAU + GlobalAccPool) the paper deploys.

``tests/test_resnet9.py`` asserts model == exported graph == streamlined
graph == Pallas-MVAU execution, value-for-value.

Structure (PEFSL ResNet-9, width w): conv(3→w) · conv(w→2w)+pool ·
residual(2w) · conv(2w→4w)+pool · conv(4w→8w)+pool · residual(8w) · GAP.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quant import QuantConfig, fake_quant, quantize, thresholds_for
from repro.core.graph import Graph, Node, im2col

Params = Dict[str, Any]


def plan(width: int = 64) -> List[Dict]:
    w = width
    return [
        dict(name="c0", cin=3, cout=w, pool=False),
        dict(name="c1", cin=w, cout=2 * w, pool=True),
        dict(name="r1a", cin=2 * w, cout=2 * w, pool=False, res_open=True),
        dict(name="r1b", cin=2 * w, cout=2 * w, pool=False, res_close=True),
        dict(name="c2", cin=2 * w, cout=4 * w, pool=True),
        dict(name="c3", cin=4 * w, cout=8 * w, pool=True),
        dict(name="r2a", cin=8 * w, cout=8 * w, pool=False, res_open=True),
        dict(name="r2b", cin=8 * w, cout=8 * w, pool=False, res_close=True),
    ]


def feature_dim(width: int = 64) -> int:
    return 8 * width


def layer_names(width: int = 64) -> List[str]:
    """Quantizable layer names, in plan order — the per-layer DSE axis."""
    return [blk["name"] for blk in plan(width)]


def coupled_act_groups(width: int = 64) -> List[List[str]]:
    """Layer groups whose ACTIVATION grids must share a fraction.

    A residual add sums the closing block's activation with the tensor that
    entered the residual pair — two different fixed-point fractions there
    would force the integer lowering to a float frontier mid-network (the
    add is only code-exact on a common frac), and the next MVAU could no
    longer lower.  Under the ``grid_point`` convention (``frac = a_bits −
    2``) a common frac means equal ``a_bits``, so a feasible mixed-precision
    plan assigns each group ONE activation width: {c1, r1b} and {c3, r2b}.
    """
    groups: List[List[str]] = []
    entry = prev = None
    for blk in plan(width):
        if blk.get("res_open"):
            entry = prev
        if blk.get("res_close") and entry is not None:
            groups.append([entry, blk["name"]])
            entry = None
        prev = blk["name"]
    return groups


def quant_layers(width: int = 64) -> Dict[str, Any]:
    """The BuildRecipe ``quant_layers`` hook: names + act couplings."""
    return {"names": layer_names(width),
            "coupled_act": coupled_act_groups(width)}


def init_params(key, width: int = 64) -> Params:
    p: Params = {}
    for blk in plan(width):
        k = 3
        fan_in = k * k * blk["cin"]
        key, sub = jax.random.split(key)
        p[blk["name"]] = {
            "w": jax.random.normal(sub, (k, k, blk["cin"], blk["cout"]),
                                   jnp.float32) * math.sqrt(2.0 / fan_in),
            "gamma": jnp.ones((blk["cout"],), jnp.float32),
            "beta": jnp.zeros((blk["cout"],), jnp.float32),
        }
    return p


# ---------------------------------------------------------------------------
# im2col conv: ``core.graph.im2col``, the graph executor's own function, so
# model == graph holds by construction. It builds the patches from static
# slices: index-array gathers become device loops on the TPU.
# ---------------------------------------------------------------------------
def _maxpool(x: jax.Array, k: int = 2) -> jax.Array:
    n, h, w, c = x.shape
    return x.reshape(n, h // k, k, w // k, k, c).max(axis=(2, 4))


def forward(params: Params, x: jax.Array, qcfg: Optional[QuantConfig] = None,
            width: int = 64) -> jax.Array:
    """x: (B, H, W, 3) NHWC in [0,1]-ish. Returns (B, 8·width) features.

    Per-layer mixed precision: each block resolves its own specs through
    ``qcfg.layer(name)`` — a uniform config (no overrides) resolves to
    itself for every layer, so the pre-PR 9 behaviour is unchanged.  The
    graph input rides the TOP-LEVEL activation grid (same convention as the
    exporter's ``x`` dtype seed and the deploy-time input quant).
    """
    as_in = qcfg.act if qcfg else None
    x = fake_quant(x, as_in)
    skip = None
    for blk in plan(width):
        p = params[blk["name"]]
        lcfg = qcfg.layer(blk["name"]) if qcfg else None
        ws = lcfg.weight if lcfg else None
        as_ = lcfg.act if lcfg else None
        w_q = fake_quant(p["w"], ws).reshape(-1, blk["cout"])
        y = jnp.matmul(im2col(x), w_q)                     # conv as im2col·W
        y = y * p["gamma"] + p["beta"]                    # BN affine (folded)
        y = jax.nn.relu(y)
        y = fake_quant(y, as_)
        if blk.get("pool"):
            y = _maxpool(y)
        if blk.get("res_open"):
            skip = x
        if blk.get("res_close"):
            y = y + skip
            skip = None
        x = y
    return jnp.mean(x, axis=(1, 2))                       # -> GAP in export


def l2_features(params: Params, x: jax.Array, qcfg=None, width: int = 64):
    f = forward(params, x, qcfg, width)
    return f / jnp.maximum(jnp.linalg.norm(f, axis=-1, keepdims=True), 1e-8)


# ---------------------------------------------------------------------------
# FINN-style export (paper Fig. 3 flow: Brevitas/ONNX -> graph)
# ---------------------------------------------------------------------------
def _block_thresholds(p: Params, aspec) -> np.ndarray:
    """Fold BN affine + ReLU + act-quant into per-channel thresholds.

    MultiThreshold output code q fires when γ·y + β ≥ T_q^grid, i.e.
    y ≥ (T_q^grid − β)/γ — BN and activation quantization vanish into
    compile-time constants (the FINN 'streamline into thresholds' move).
    Requires γ > 0 (true at init and preserved by the trainer's
    reparameterization γ = exp(·); asserted at export).
    """
    grid = thresholds_for(aspec)                          # (L,)
    gamma = np.asarray(p["gamma"], np.float64)
    beta = np.asarray(p["beta"], np.float64)
    assert (gamma > 0).all(), "BN scale must stay positive for threshold folding"
    t = (grid[None, :] - beta[:, None]) / gamma[:, None]  # (C, L)
    return t.astype(np.float32)


def export_graph(params: Params, qcfg: QuantConfig, width: int = 64,
                 img: int = 32, insert_transposes: bool = True) -> Graph:
    """Produce the pre-streamline dataflow graph.

    ``insert_transposes=True`` reproduces the PyTorch-export artifact the
    paper fixes: a Transpose(NHWC→NCHW) lands between each conv-MatMul and
    its MultiThreshold, and Transpose(NCHW→NHWC) follows before the next
    im2col (Fig. 4).  The streamline pipeline must absorb/cancel them all.
    """
    nodes: List[Node] = []
    inits: Dict[str, np.ndarray] = {}
    src = "x"  # NHWC, already on the activation grid
    hw = img
    skip_src = None

    for blk in plan(width):
        nm = blk["name"]
        p = params[blk["name"]]
        lcfg = qcfg.layer(nm)                 # per-layer specs (self if uniform)
        ws, as_ = lcfg.weight, lcfg.act
        w_q = np.asarray(fake_quant(p["w"], ws)).reshape(-1, blk["cout"])
        inits[f"{nm}_w"] = w_q.astype(np.float32)
        inits[f"{nm}_t"] = _block_thresholds(p, as_)

        nodes.append(Node("im2col", [src], [f"{nm}_col"],
                          {"kernel": 3, "stride": 1, "pad": 1}))
        nodes.append(Node("matmul", [f"{nm}_col", f"{nm}_w"], [f"{nm}_mm"]))
        mm_out = f"{nm}_mm"
        if insert_transposes:
            nodes.append(Node("transpose", [mm_out], [f"{nm}_nchw"],
                              {"perm": [0, 3, 1, 2]}))
            nodes.append(Node("multithreshold", [f"{nm}_nchw", f"{nm}_t"],
                              [f"{nm}_mt_nchw"],
                              {"channel_axis": 1, "out_base": 0,
                               "out_scale": as_.scale}))
            nodes.append(Node("transpose", [f"{nm}_mt_nchw"], [f"{nm}_act"],
                              {"perm": [0, 2, 3, 1]}))
        else:
            nodes.append(Node("multithreshold", [mm_out, f"{nm}_t"],
                              [f"{nm}_act"],
                              {"channel_axis": -1, "out_base": 0,
                               "out_scale": as_.scale}))
        cur = f"{nm}_act"
        if blk.get("pool"):
            nodes.append(Node("maxpool", [cur], [f"{nm}_pool"], {"kernel": 2}))
            cur = f"{nm}_pool"
            hw //= 2
        if blk.get("res_open"):
            skip_src = src
        if blk.get("res_close"):
            nodes.append(Node("add", [cur, skip_src], [f"{nm}_res"]))
            cur = f"{nm}_res"
            skip_src = None
        src = cur

    nodes.append(Node("reduce_mean", [src], ["features"],
                      {"axes": [1, 2], "spatial_size": hw * hw}))
    g = Graph(nodes, ["x"], ["features"], inits, name="resnet9")
    # Datatype seeds for InferDataTypes (core/datatypes.py): the input rides
    # the activation grid, weight initializers the weight grid; threshold
    # tables are float compile-time constants until integer lowering.
    g.dtypes["x"] = qcfg.act
    for blk in plan(width):
        g.dtypes[f"{blk['name']}_w"] = qcfg.layer(blk["name"]).weight
        g.dtypes[f"{blk['name']}_t"] = None
    return g


# ---------------------------------------------------------------------------
# Build recipe — registered HERE so new backbones plug into repro.compile()
# without touching repro/core (paper Sec. III-A: step lists belong to the
# architecture, not the framework).
# ---------------------------------------------------------------------------
def _export_for_compile(params: Params, qcfg: QuantConfig, img: int = 32) -> Graph:
    """Recipe exporter: infer width from the param tree, export the graph."""
    if qcfg is None:
        raise ValueError("repro.compile(resnet9_params, qcfg): qcfg is "
                         "required to place thresholds on the bit-width grid")
    width = int(np.shape(params["c0"]["w"])[-1])
    return export_graph(params, qcfg, width=width, img=img)


def _register_recipe():
    from repro.core.recipes import register_recipe

    register_recipe(
        "resnet9",
        ["convert_reduce_mean_to_gap",
         "absorb_transpose_into_multithreshold",
         "cancel_transpose_pairs",
         "move_mul_past_matmul",
         "collapse_repeated_mul",
         "fold_mul_into_multithreshold",
         "fuse_matmul_threshold_to_mvau",
         "verify_hw_mappable"],
        description="paper's customized ResNet-9 flow (Sec. III-C/D fixes)",
        exporter=_export_for_compile,
        init_params=init_params,
        feature_dim=feature_dim,
        forward=forward,
        quant_layers=quant_layers)


_register_recipe()
