"""Vision transformer (DINO ViT-S/16) — a few-shot backbone, quantization-aware.

DINO (Caron et al., arXiv 2104.14294) at DeiT-S widths (arXiv 2012.12877),
the backbone of the P>M>F prototype classifier (arXiv 2204.07305): 16x16
patches of a 224x224 frame, a class token and learned positions (197
tokens), width 384, 6 heads of 64, 12 pre-norm layers, each LayerNorm (eps
1e-6) → qkv with bias → bidirectional softmax attention → projection →
residual add, LayerNorm → MLP of 1536 with GELU → residual add.  The
feature is the class token after the final LayerNorm.

W8A8 on the integer datapath: an activation quantizer sits at the input of
every linear (after each LayerNorm, after attention, after GELU; the frame
rides the same grid), so every linear's operands are 8-bit codes and run as
``matmul_int`` (``int8-dot`` on the chip).  Attention is integer too, as in
FQ-ViT and I-ViT: q, k and v are put on the same grid, and the scores, an
integer softmax (I-BERT's exp) and the weighted sum of v are exact
(``kernels.ref.attn_full``).  LayerNorm and GELU are integer inside, as in
I-BERT (``core.graph.layernorm`` and ``igelu``): each rounds its float32
input onto a grid and returns float32 values on the next quantizer's grid.
The biases and the residual stream stay float32, where each value is one
exact or correctly rounded operation, so the features have the same bits
in any program.

Two execution forms, numerically identical by construction (the same
helpers from ``core.graph`` and ``kernels.ref``):

1. **QAT model** (:func:`forward`), with fake-quantized weights and
   activations;
2. **Exported dataflow graph** (:func:`export_graph`): linears as ``matmul``
   + bias ``add`` with quantized weight initializers, each activation
   quantizer a canonical-grid ``multithreshold`` that integer lowering
   streamlines to ``quantize``, attention as one ``attn_full`` node on the
   quantized q|k|v (the Pallas kernel on the chip), LayerNorm and GELU as
   ``layernorm`` and ``igelu`` nodes with their grids as attributes.

The structure (width, heads, depth, patch, frame size, MLP width) is read
from the parameter shapes, so one recipe serves every size: the qkv weight
is stored ``(d, 3, heads, d / heads)``.  Registers the recipe ``"vit-s16"``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import (Graph, Node, igelu, im2col, layernorm,
                              vit_tokens)
from repro.core.quant import QuantConfig, fake_quant, thresholds_for
from repro.kernels import ref

Params = Dict[str, Any]

EPS = 1e-6
GELU_IN_FRAC = 8        # GELU reads its input on the grid 2**-8
_HIGHEST = jax.lax.Precision.HIGHEST
LINEARS = ("qkv", "proj", "fc1", "fc2")


@dataclasses.dataclass(frozen=True)
class Dims:
    width: int
    heads: int
    depth: int
    patch: int
    img: int
    mlp: int


def dims(params: Params) -> Dims:
    """The structure a parameter tree holds, from its shapes."""
    p = np.shape(params["patch"]["w"])[0]
    grid = math.isqrt(np.shape(params["pos"])[0] - 1)
    qkv = np.shape(params["blocks"][0]["qkv"]["w"])
    return Dims(width=qkv[0], heads=qkv[2], depth=len(params["blocks"]),
                patch=p, img=grid * p,
                mlp=np.shape(params["blocks"][0]["fc1"]["w"])[1])


def feature_dim(width: int = 384) -> int:
    return width


def layer_names(depth: int = 12) -> List[str]:
    """The linears, in order: the per-layer bit-width axis."""
    return ["patch"] + [f"l{i}.{n}" for i in range(depth) for n in LINEARS]


def quant_layers(width: int = 384) -> Dict[str, Any]:
    """The BuildRecipe ``quant_layers`` hook.  Each activation quantizer
    feeds one linear and the residual stream is float, so no two layers'
    activation grids are coupled."""
    return {"names": layer_names(), "coupled_act": []}


def init_params(key, width: int = 384) -> Params:
    """The DeiT initialisation of a ViT/16 of ``width`` at 224x224 (heads of
    64, 12 layers, an MLP of 4·width): truncated-normal (std 0.02) weights,
    positions and class token, zero biases, unit LayerNorm gains."""
    heads, depth, patch, img, mlp = width // 64, 12, 16, 224, 4 * width
    hd = width // heads
    keys = iter(jax.random.split(key, 3 + 4 * depth))

    def w(shape):
        return jax.random.truncated_normal(next(keys), -2.0, 2.0, shape,
                                           jnp.float32) * 0.02

    def ln():
        return {"g": jnp.ones((width,), jnp.float32),
                "b": jnp.zeros((width,), jnp.float32)}

    def lin(shape, n_out):
        return {"w": w(shape), "b": jnp.zeros(n_out, jnp.float32)}

    grid = img // patch
    return {
        "patch": lin((patch, patch, 3, width), (width,)),
        "cls": w((width,)),
        "pos": w((1 + grid * grid, width)),
        "blocks": [{"ln1": ln(),
                    "qkv": lin((width, 3, heads, hd), (3, heads, hd)),
                    "proj": lin((width, width), (width,)),
                    "ln2": ln(),
                    "fc1": lin((width, mlp), (mlp,)),
                    "fc2": lin((mlp, width), (width,))}
                   for _ in range(depth)],
        "norm": ln(),
    }


def _weight(params: Params, name: str):
    """(K, N) weight and (N,) bias of the linear ``name`` (``patch`` or
    ``l<i>.<linear>``)."""
    if name == "patch":
        w = params["patch"]["w"]
        return w.reshape(-1, w.shape[-1]), params["patch"]["b"]
    i, lin = name[1:].split(".")
    p = params["blocks"][int(i)][lin]
    return p["w"].reshape(p["w"].shape[0], -1), p["b"].reshape(-1)


def res_frac(qcfg: QuantConfig) -> int:
    """Fraction bits of the grid on which LayerNorm reads the residual
    stream: the linears' accumulator grid (weight step times activation
    step) of the top-level specs."""
    return qcfg.weight.frac_bits + qcfg.act.frac_bits


def forward(params: Params, x: jax.Array, qcfg: QuantConfig,
            width: Optional[int] = None) -> jax.Array:
    """x: (B, H, W, 3) NHWC in [0, 1]. Returns (B, width) features: the
    class token after the final LayerNorm.

    Each linear resolves its weight grid and its input's activation grid
    through ``qcfg.layer(name)``; the frame rides the top-level activation
    grid (as the exporter's ``x`` seed and the deploy-time input quant).
    LayerNorm and GELU are integer (``core.graph``): each LayerNorm reads
    the residual stream on :func:`res_frac`'s grid and writes the next
    quantizer's grid (the final one, its own input grid), and GELU reads
    ``GELU_IN_FRAC`` bits."""
    if qcfg is None:
        raise ValueError("the ViT's integer LayerNorm and GELU need a "
                         "QuantConfig for their grids")
    dm = dims(params)
    rf = res_frac(qcfg)

    def specs(name):
        lq = qcfg.layer(name)
        return lq.weight, lq.act

    def linear(h, name):
        ws, _ = specs(name)
        w, b = _weight(params, name)
        return jnp.matmul(h, fake_quant(w, ws), precision=_HIGHEST) + b

    def quant_for(h, name):
        return fake_quant(h, specs(name)[1])

    def norm(t, p, name):
        out = specs(name)[1].frac_bits if name else rf
        return layernorm(t, p["g"], p["b"], EPS, rf, out)

    x = fake_quant(x, qcfg.act)
    t = linear(im2col(x, dm.patch, dm.patch, 0), "patch")
    t = vit_tokens(t, params["cls"], params["pos"])
    for i, blk in enumerate(params["blocks"]):
        p = f"l{i}."
        h = norm(t, blk["ln1"], p + "qkv")
        qkv = linear(quant_for(h, p + "qkv"), p + "qkv")
        a = ref.attn_full(quant_for(qkv, p + "attn"), dm.heads,
                          specs(p + "attn")[1].frac_bits)
        t = t + linear(quant_for(a, p + "proj"), p + "proj")
        h = norm(t, blk["ln2"], p + "fc1")
        u = igelu(linear(quant_for(h, p + "fc1"), p + "fc1"), GELU_IN_FRAC,
                  specs(p + "fc2")[1].frac_bits)
        t = t + linear(quant_for(u, p + "fc2"), p + "fc2")
    return norm(t[:, 0], params["norm"], None)


# ---------------------------------------------------------------------------
# Export (the same network as a dataflow graph)
# ---------------------------------------------------------------------------
def export_graph(params: Params, qcfg: QuantConfig,
                 name: str = "vit") -> Graph:
    """The pre-lowering dataflow graph: input ``x`` (B, H, W, 3) on the
    activation grid, output ``features`` (B, width)."""
    dm = dims(params)
    nodes: List[Node] = []
    inits: Dict[str, np.ndarray] = {}
    dtypes: Dict[str, Any] = {"x": qcfg.act}

    def const(nm, arr):
        inits[nm] = np.asarray(arr, np.float32)
        dtypes[nm] = None
        return nm

    def act_quant(src, lname):
        """FINN activation quantizer at a linear's or the attention's input:
        multithreshold over the canonical grid of its activation spec, one
        table per node (integer lowering rewrites tables in place)."""
        aspec = qcfg.layer(lname).act
        out = f"{lname}.aq"
        t = const(out + "_t", thresholds_for(aspec))
        nodes.append(Node("multithreshold", [src, t], [out],
                          {"channel_axis": -1, "out_base": aspec.qmin,
                           "out_scale": aspec.scale}))
        return out

    def linear(src, lname):
        ws = qcfg.layer(lname).weight
        w, b = _weight(params, lname)
        w_nm = f"{lname}.w"
        inits[w_nm] = np.asarray(fake_quant(jnp.asarray(w), ws), np.float32)
        dtypes[w_nm] = ws
        nodes.append(Node("matmul", [src, w_nm], [f"{lname}.mm"]))
        nodes.append(Node("add", [f"{lname}.mm", const(f"{lname}.b", b)],
                          [f"{lname}.y"]))
        return f"{lname}.y"

    rf = res_frac(qcfg)

    def norm(src, p, out, lname):
        """Integer LayerNorm onto the grid of ``lname``'s input quantizer
        (the final one, ``lname`` None, onto its own input grid)."""
        nodes.append(Node("layernorm", [src, const(out + "_g", p["g"]),
                                        const(out + "_b", p["b"])],
                          [out], {"eps": EPS, "in_frac": rf,
                                  "out_frac": qcfg.layer(lname).act.frac_bits
                                  if lname else rf}))
        return out

    nodes.append(Node("im2col", ["x"], ["cols"],
                      {"kernel": dm.patch, "stride": dm.patch, "pad": 0}))
    pe = linear("cols", "patch")
    nodes.append(Node("vit_tokens", [pe, const("cls", params["cls"]),
                                     const("pos", params["pos"])], ["t0"]))
    t = "t0"
    for i, blk in enumerate(params["blocks"]):
        p = f"l{i}."
        h = norm(t, blk["ln1"], p + "ln1", p + "qkv")
        if qcfg.layer(p + "attn").act.total_bits > 8:
            raise ValueError("the integer attention takes 8-bit codes")
        qkv = act_quant(linear(act_quant(h, p + "qkv"), p + "qkv"),
                        p + "attn")
        nodes.append(Node("attn_full", [qkv], [p + "attn"],
                          {"heads": dm.heads,
                           "frac_bits": qcfg.layer(p + "attn").act.frac_bits}))
        o = linear(act_quant(p + "attn", p + "proj"), p + "proj")
        nodes.append(Node("add", [t, o], [p + "r1"]))
        h = norm(p + "r1", blk["ln2"], p + "ln2", p + "fc1")
        u = linear(act_quant(h, p + "fc1"), p + "fc1")
        nodes.append(Node("igelu", [u], [p + "gelu"],
                          {"in_frac": GELU_IN_FRAC,
                           "out_frac": qcfg.layer(p + "fc2").act.frac_bits}))
        o = linear(act_quant(p + "gelu", p + "fc2"), p + "fc2")
        nodes.append(Node("add", [p + "r1", o], [p + "r2"]))
        t = p + "r2"
    nodes.append(Node("cls_readout", [t], ["cls_out"]))
    norm("cls_out", params["norm"], "features", None)
    g = Graph(nodes, ["x"], ["features"], inits, name=name)
    g.dtypes.update(dtypes)
    return g


def _export_for_compile(params: Params, qcfg: QuantConfig) -> Graph:
    if qcfg is None:
        raise ValueError("repro.compile(vit_params, qcfg): qcfg is required "
                         "to place the quantizers on the bit-width grid")
    return export_graph(params, qcfg)


def _register_recipe():
    from repro.core.recipes import register_recipe

    register_recipe(
        "vit-s16",
        [],   # datatype passes ride in via repro.compile(datapath="int"); no
              # CNN streamlining, and float attention is not HW-mappable
        description=("DINO ViT-S/16 few-shot backbone: datatype inference + "
                     "integer lowering, integer attention on the Pallas kernel"),
        exporter=_export_for_compile,
        init_params=init_params,
        feature_dim=feature_dim,
        forward=forward,
        quant_layers=quant_layers)


_register_recipe()
