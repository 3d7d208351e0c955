"""Plain reference of DINO ViT-S/16 as a few-shot backbone.

Written from the published description (DINO, arXiv 2104.14294, at the
DeiT-S widths of arXiv 2012.12877; the backbone of P>M>F, arXiv
2204.07305) in straightforward ``jax.numpy`` at float32 under
``jax.default_matmul_precision("highest")``.  It imports nothing of the
program under test.  Its one tie to the program is :func:`make_params`,
which makes the weights the benchmark serves, in the layout the program
takes (the qkv weight as ``(width, 3, heads, head size)``).

Model, per frame (NHWC, values in [0, 1]):

* the frame is put on the activation grid (round half to even, saturate);
* 16x16 patches, each flattened in (row, column, channel) order, times the
  patch weight plus its bias; the class token first, then the patches in
  row-major order, plus the learned positions: 197 tokens of 384;
* 12 pre-norm layers: LayerNorm (eps 1e-6) -> activation grid -> qkv with
  bias -> activation grid -> bidirectional softmax attention, 6 heads of
  64, on the codes (below) -> activation grid -> projection with bias ->
  residual add; LayerNorm -> activation grid -> linear to 1536 with bias ->
  GELU -> activation grid -> linear to 384 with bias -> residual add;
* the feature: the class token after the final LayerNorm, on the grid of
  the linears' sums (2**-13).

Every linear multiplies an activation on the activation grid by a weight on
the weight grid.  It is computed exactly: the integer codes multiply in
int32 (a sum of 1536 products of 8-bit codes can pass 2**24, where float32
would round), and the sum is scaled by the grids' step.

Attention is integer, as in FQ-ViT and I-ViT.  The scores are the products
of q and k codes; a score ``n`` grid steps (of ``2**-2f / sqrt(64)``) below
its row's largest, ``n = z·floor(ln 2 / step) + r``, has the exp term
``((B - r)^2 + C) >> (z + shift)``: I-BERT's second-order exp (arXiv
2101.01321), ``exp(p) ~ 0.3585 (p + 1.353)^2 + 0.344`` on (-ln 2, 0], with
``B`` and ``C`` those constants on the score grid, ``n`` capped where the
term is 0, and ``shift`` keeping each term below 2**14.  The weights are the
terms' shares floored onto 2**-16; the output is the weights times the v
codes, exact, on the grid 2**-(16 + f).

LayerNorm and GELU are integer too, as in I-BERT (:func:`_layernorm`,
:func:`_gelu`): each puts its float32 input on a grid (LayerNorm on the
linears' grid 2**-13, GELU on 2**-8) and returns the next quantizer's grid
(the final LayerNorm, 2**-13).  LayerNorm scales the deviations from the
mean to 12 bits, takes an integer square root of their mean square and
divides; GELU is x times I-BERT's second-order fit of the normal CDF.

So the only float rounding left is one operation per value: a bias added to
a linear's exact sum, and a residual add.  Any program that computes the
same model rounds the same way and agrees to the bit.

Departures from DINO: no ImageNet input normalisation (an affine per
channel that folds into the patch embedding's weights and bias), frames in
[0, 1] on the activation grid (about 5-bit pixels), one per-tensor
power-of-two grid per kind, q, k and v on the activation grid, the integer
softmax, LayerNorm and GELU above, and random weights (:func:`make_params`).

Work (:func:`weight_matmuls`, :func:`macs_per_frame`, :func:`attn_work`):
the benchmark's readers count the operations of a served frame from these.

Few-shot head: L2-normalised features, class means of the normalised
support features, L2-normalised again; a query's similarity to a class is
the cosine between its normalised feature and the normalised mean.

The control variant ``"bf16"`` computes the same model one step lower in
precision: the float values between quantizers in bfloat16 (every linear's
output after its bias, and the residual stream after every add).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6


def shape(cfg: Dict) -> Dict[str, int]:
    """Width, heads, depth, patch, frame side, MLP width and tokens."""
    out = {k: int(cfg[k]) for k in ("width", "heads", "depth", "patch",
                                     "img", "mlp")}
    out["tokens"] = 1 + (out["img"] // out["patch"]) ** 2
    return out


def weight_matmuls(cfg: Dict) -> List[Tuple[str, int, int, int]]:
    """Every weight matmul of one forward pass of one frame as ``(name, rows
    per frame, K, N)``: the patch embedding (one row per patch), then per
    layer qkv, projection and the two MLP linears (one row per token)."""
    s = shape(cfg)
    d, n, m = s["width"], s["tokens"], s["mlp"]
    out = [("patch", n - 1, 3 * s["patch"] ** 2, d)]
    for i in range(s["depth"]):
        out += [(f"l{i}.qkv", n, d, 3 * d), (f"l{i}.proj", n, d, d),
                (f"l{i}.fc1", n, d, m), (f"l{i}.fc2", n, m, d)]
    return out


def attn_work(cfg: Dict) -> Tuple[int, int]:
    """``(flops, bytes)`` of one layer's attention for one frame, at the
    real token count: QK^T and PV, 2·S²·d multiply-adds, are 4·S²·d
    operations; q|k|v (S, 3d) float32 is read and the (S, d) output
    written."""
    s = shape(cfg)
    n, d = s["tokens"], s["width"]
    return 4 * n * n * d, 4 * (n * 3 * d + n * d)


def macs_per_frame(cfg: Dict) -> int:
    """Every multiply-accumulate of one forward pass of one frame: the
    weight matmuls' and attention's (QK^T and PV, S·S·d each, per layer)."""
    s = shape(cfg)
    weights = sum(m * k * n for _, m, k, n in weight_matmuls(cfg))
    return weights + s["depth"] * attn_work(cfg)[0] // 2


def key_for(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size, beyond 32 bits too."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


# Each linear's weights are drawn N(0, (gain / sqrt(K))^2): its output then
# has about gain times its input's root mean square.  qkv at 1.25 makes the
# attention logits' spread about 1.7, so a query favours a few keys, and
# puts about 0.5% of the q, k and v codes in saturation.
GAIN = {"patch": 1.0, "qkv": 1.25, "proj": 1.0, "fc1": 1.0, "fc2": 1.0}
BIAS_STD = 0.1          # every linear's bias
TOKEN_STD = 0.5         # the class token and the positions
LN_GAIN = (0.5, 1.5)    # LayerNorm gains, uniform
LN_SHIFT = 0.25         # LayerNorm shifts, uniform in [-LN_SHIFT, LN_SHIFT]
PIXEL_MEAN = 0.5        # subtracted from every pixel, in the patch bias


def make_params(seed: int, cfg: Dict) -> Dict:
    """Untrained weights from ``seed``, made on the device in one jitted
    call: linear weights normal with standard deviation ``GAIN / sqrt(K)``,
    biases normal (0.1), LayerNorm gains uniform in [0.5, 1.5] and shifts in
    [-0.25, 0.25], the class token and positions normal (0.5).  float32,
    the type the program's deploy takes them in."""
    s = shape(cfg)
    d, h, m, p = s["width"], s["heads"], s["mlp"], s["patch"]

    @jax.jit
    def make(key):
        keys = iter(jax.random.split(key, 3 + 12 * s["depth"] + 4))

        def normal(shp, std):
            return jax.random.normal(next(keys), shp, jnp.float32) * std

        def lin(name, shp, k, n_out):
            return {"w": normal(shp, GAIN[name] / math.sqrt(k)),
                    "b": normal(n_out, BIAS_STD)}

        def ln():
            return {"g": jax.random.uniform(next(keys), (d,), jnp.float32,
                                            *LN_GAIN),
                    "b": jax.random.uniform(next(keys), (d,), jnp.float32,
                                            -LN_SHIFT, LN_SHIFT)}

        patch = lin("patch", (p, p, 3, d), 3 * p * p, (d,))
        # the input normalisation x - PIXEL_MEAN, folded into the bias
        patch["b"] = patch["b"] - PIXEL_MEAN * patch["w"].sum(axis=(0, 1, 2))
        return {
            "patch": patch,
            "cls": normal((d,), TOKEN_STD),
            "pos": normal((s["tokens"], d), TOKEN_STD),
            "blocks": [{"ln1": ln(),
                        "qkv": lin("qkv", (d, 3, h, d // h), d,
                                   (3, h, d // h)),
                        "proj": lin("proj", (d, d), d, (d,)),
                        "ln2": ln(),
                        "fc1": lin("fc1", (d, m), d, (m,)),
                        "fc2": lin("fc2", (m, d), m, (d,))}
                       for _ in range(s["depth"])],
            "norm": ln(),
        }

    return make(key_for(seed))


def _codes(x, spec: Dict):
    """Round half to even onto a fixed-point grid, saturating: int32 codes."""
    lo = -(2 ** (spec["total_bits"] - 1)) if spec["signed"] else 0
    hi = 2 ** (spec["total_bits"] - 1) - 1 if spec["signed"] else \
        2 ** spec["total_bits"] - 1
    q = jnp.clip(jnp.round(x * 2.0 ** spec["frac_bits"]), lo, hi)
    return q.astype(jnp.int32)


def _bf16(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _round(x, control):
    """A float value between quantizers: float32, or bfloat16 in the
    control."""
    return _bf16(x) if control == "bf16" else x


def _linear(h_codes, w, b, wq: Dict, aq: Dict, control=None):
    """Exact product of activation codes and the (K, N) weight's codes,
    accumulated in int32 (the 8-bit codes go in as int8, which the chip
    multiplies exactly), scaled by the grids' step, plus the bias."""
    assert max(wq["total_bits"], aq["total_bits"]) <= 8, "int8 operands"
    acc = jnp.matmul(h_codes.astype(jnp.int8), _codes(w, wq).astype(jnp.int8),
                     preferred_element_type=jnp.int32)
    step = 2.0 ** -(aq["frac_bits"] + wq["frac_bits"])
    return _round(acc.astype(jnp.float32) * step + b.reshape(-1), control)


def _dense(h_codes, p, wq: Dict, aq: Dict, control=None):
    return _linear(h_codes, p["w"].reshape(p["w"].shape[0], -1), p["b"],
                   wq, aq, control)


# LayerNorm and GELU in integer arithmetic, as in I-BERT (arXiv 2101.01321)
LN_IN_BITS = 23         # LayerNorm input codes within +-2**23
LN_SQ_BITS = 11         # deviations scaled to at most 2**11 before squaring
LN_GAIN_FRAC = 10       # gains on the grid 2**-10 (codes within +-2**12)
LN_SIGMA_FRAC = 4       # the standard deviation to 1/16 of a unit
GELU_IN_FRAC = 8        # GELU's input on the grid 2**-8 (codes within +-2**15)
GELU_PHI_FRAC = 10      # the normal CDF on the grid 2**-10
IGELU_A, IGELU_B = 0.2888, 1.769


def _int_codes(x, frac: int, bits: int):
    """Round half to even onto the grid 2**-frac, within +-2**bits."""
    q = jnp.round(x * 2.0 ** frac)
    return jnp.clip(q, -(2 ** bits), 2 ** bits - 1).astype(jnp.int32)


def _sqrt_floor(v):
    """floor(sqrt(v)) for int32 v >= 1: Newton from 46341 >= sqrt(2**31),
    each step min(x, (x + v // x) // 2), 20 steps."""
    x = jnp.full_like(v, 46341)
    for _ in range(20):
        x = jnp.minimum(x, (x + v // x) // 2)
    return x


def _layernorm(x, p, in_frac: int, out_frac: int):
    """Integer LayerNorm of the rows of x (float32) onto 2**-out_frac.

    c: the codes of x on 2**-in_frac.  d = c - round(mean c) (half up).  e =
    d / 2**k rounded half up, k the least whole number with max |d| <
    2**(11 + k).  var = floor(sum e^2 / n).  sigma = floor(sqrt(var * 2**8
    + max(floor(eps * 2**(2 in_frac + 8) / 4**k), 1))), the standard
    deviation of e in sixteenths.  The output code is e * g / sigma (g's
    code on 2**-10) on 2**-out_frac, rounded half up, plus b's code on
    2**-out_frac."""
    n = x.shape[-1]
    c = _int_codes(x, in_frac, LN_IN_BITS)
    total = jnp.sum(c, axis=-1, keepdims=True)
    d = c - jnp.floor_divide(total + n // 2, n)
    big = jnp.max(jnp.abs(d), axis=-1, keepdims=True)
    k = jnp.zeros_like(big)
    for j in range(LN_SQ_BITS, LN_IN_BITS + 2):
        k = k + (big >= 2 ** j).astype(jnp.int32)
    half = jnp.where(k > 0, jnp.left_shift(1, jnp.maximum(k - 1, 0)), 0)
    e = jnp.right_shift(d + half, k)
    var = jnp.floor_divide(jnp.sum(e * e, axis=-1, keepdims=True), n)
    eps = jnp.right_shift(round(EPS * 2.0 ** (2 * in_frac + 2 * LN_SIGMA_FRAC)),
                          2 * k)
    sigma = _sqrt_floor(var * 2 ** (2 * LN_SIGMA_FRAC) + jnp.maximum(eps, 1))
    num = e * _int_codes(p["g"], LN_GAIN_FRAC, 12)
    # twice the quotient num * 2**(out_frac + 4 - 10) / sigma, floored, in
    # two steps so that no product passes 2**31
    s = out_frac + LN_SIGMA_FRAC - LN_GAIN_FRAC + 1
    if s > 0:
        whole = jnp.floor_divide(num, sigma)
        rest = num - whole * sigma
        twice = whole * 2 ** s + jnp.floor_divide(rest * 2 ** s, sigma)
    else:
        twice = jnp.floor_divide(num, sigma * 2 ** -s)
    code = jnp.floor_divide(twice + 1, 2) + _int_codes(p["b"], out_frac, 20)
    return code.astype(jnp.float32) / 2.0 ** out_frac


def _gelu(x, out_frac: int):
    """I-BERT's integer GELU onto 2**-out_frac: x Phi(x), Phi(x) = (1 +
    erf(x / sqrt 2)) / 2, with erf(u) ~ sign(u) (1 - 0.2888 (min(|u|,
    1.769) - 1.769)^2).  On codes q of x on 2**-8: t = min(|q|, qb) - qb,
    qb the code of 1.769 sqrt 2; Phi's tail 0.2888 t^2 / 2**18 on 2**-10
    (the constant on 2**-12), rounded half up; Phi = 1 - tail for q > 0,
    else tail; the product q Phi rounded half up onto 2**-out_frac."""
    q = _int_codes(x, GELU_IN_FRAC, 15)
    qb = round(IGELU_B * math.sqrt(2.0) * 2 ** GELU_IN_FRAC)
    t = jnp.minimum(jnp.abs(q), qb) - qb
    s = 2 * GELU_IN_FRAC + 14 - GELU_PHI_FRAC
    tail = jnp.floor_divide(t * t * round(IGELU_A * 2 ** 12) + 2 ** (s - 1),
                            2 ** s)
    phi = jnp.where(q > 0, 2 ** GELU_PHI_FRAC - tail, tail)
    s = GELU_IN_FRAC + GELU_PHI_FRAC - out_frac
    code = jnp.floor_divide(q * phi + 2 ** (s - 1), 2 ** s)
    return code.astype(jnp.float32) / 2.0 ** out_frac


# I-BERT's integer exp: exp(p) ~ EXP_A (p + EXP_B)^2 + EXP_C on (-ln 2, 0]
EXP_A, EXP_B, EXP_C = 0.3585, 1.353, 0.344
EXP_BITS = 14           # each exp term below 2**EXP_BITS
WEIGHT_BITS = 16        # attention weights on the grid 2**-WEIGHT_BITS


def _attention(qkv_codes, heads: int, aq: Dict):
    """Integer bidirectional attention of (n, s, 3d) q|k|v codes."""
    n, s, d3 = qkv_codes.shape
    d = d3 // 3
    hd = d // heads
    q, k, v = (qkv_codes[..., i * d:(i + 1) * d].reshape(n, s, heads, hd)
               .astype(jnp.float32) for i in range(3))
    step = 2.0 ** (-2 * aq["frac_bits"]) / math.sqrt(hd)
    ln2 = math.floor(math.log(2) / step)
    b = math.floor(EXP_B / step)
    c = math.floor(EXP_C / (EXP_A * step * step))
    shift = max(0, (b * b + c).bit_length() - EXP_BITS)
    # code products: float32 sums of integers below 2**24, exact
    scores = jnp.einsum("nqhe,nkhe->nhqk", q, k).astype(jnp.int32)
    below = jnp.minimum(jnp.max(scores, axis=-1, keepdims=True) - scores,
                        (31 - shift) * ln2)
    z, r = below // ln2, below % ln2
    e = ((b - r) * (b - r) + c) >> (z + shift)
    w = (e << WEIGHT_BITS) // jnp.sum(e, axis=-1, keepdims=True)
    out = jnp.einsum("nhqk,nkhe->nqhe", w.astype(jnp.float32), v)
    return out.reshape(n, s, d) * 2.0 ** -(WEIGHT_BITS + aq["frac_bits"])


def _forward(params, x, cfg, control):
    s = shape(cfg)
    wq, aq = cfg["quant"]["weight"], cfg["quant"]["act"]
    rf = wq["frac_bits"] + aq["frac_bits"]      # the residual's grid
    n, p = x.shape[0], s["patch"]
    g = s["img"] // p
    xc = _codes(x, aq)
    patches = xc.reshape(n, g, p, g, p, 3).transpose(0, 1, 3, 2, 4, 5)
    t = _linear(patches.reshape(n, g * g, p * p * 3),
                params["patch"]["w"].reshape(-1, s["width"]),
                params["patch"]["b"], wq, aq, control)
    cls = jnp.broadcast_to(params["cls"], (n, 1, s["width"]))
    t = _round(jnp.concatenate([cls, t], axis=1) + params["pos"], control)
    for blk in params["blocks"]:
        h = _codes(_layernorm(t, blk["ln1"], rf, aq["frac_bits"]), aq)
        qkv = _codes(_dense(h, blk["qkv"], wq, aq, control), aq)
        a = _attention(qkv, s["heads"], aq)
        t = _round(t + _dense(_codes(a, aq), blk["proj"], wq, aq, control),
                   control)
        h = _codes(_layernorm(t, blk["ln2"], rf, aq["frac_bits"]), aq)
        u = _gelu(_dense(h, blk["fc1"], wq, aq, control), aq["frac_bits"])
        t = _round(t + _dense(_codes(u, aq), blk["fc2"], wq, aq, control),
                   control)
    return _layernorm(t[:, 0], params["norm"], rf, rf)


@functools.partial(jax.jit, static_argnames=("cfg_key", "control"))
def _features(params, x, cfg_key, control):
    cfg = dict(cfg_key)
    cfg["quant"] = {k: dict(v) for k, v in cfg["quant"]}
    with jax.default_matmul_precision("highest"):
        return _forward(params, x, cfg, control)


def _freeze(cfg: Dict):
    q = tuple((k, tuple(sorted(v.items())))
              for k, v in sorted(cfg["quant"].items()))
    return tuple((k, int(cfg[k])) for k in
                 ("width", "heads", "depth", "patch", "img", "mlp")) + \
        (("quant", q),)


def features(params, x, cfg: Dict, control: Optional[str] = None,
             block: int = 64) -> np.ndarray:
    """(n, H, W, 3) frames -> (n, width) features, in blocks of ``block``
    frames so that the reference fits beside anything else."""
    x = np.asarray(x, np.float32)
    key = _freeze(cfg)
    out = []
    for i in range(0, x.shape[0], block):
        chunk = x[i:i + block]
        n = chunk.shape[0]
        if n < block:
            chunk = np.concatenate(
                [chunk, np.zeros((block - n,) + chunk.shape[1:], np.float32)])
        out.append(np.asarray(_features(params, jnp.asarray(chunk), key,
                                        control))[:n])
    return np.concatenate(out) if out else np.zeros((0, int(cfg["width"])))


def _l2(x: np.ndarray) -> np.ndarray:
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-8)


def prototypes(support_feats: np.ndarray, labels: np.ndarray,
               n_way: int) -> np.ndarray:
    """(n_way, D) normalised class means of normalised support features."""
    f = _l2(np.asarray(support_feats, np.float64))
    means = np.stack([f[labels == c].mean(axis=0) for c in range(n_way)])
    return _l2(means)


def sims(query_feats: np.ndarray, protos: np.ndarray) -> np.ndarray:
    """(Q, C) cosine similarities, in float64 on the host."""
    return _l2(np.asarray(query_feats, np.float64)) @ protos.T
