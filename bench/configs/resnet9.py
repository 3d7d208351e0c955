"""Plain reference of the PEFSL/EASY ResNet-9 few-shot classifier.

Written from the published description (arXiv 2602.16024; PEFSL, EASY) in
straightforward ``jax.numpy`` at float32 and ``HIGHEST`` matmul precision,
with the batch-norm affine and its rounding exact.
It imports nothing of the program under test.  Its one tie to the program
is :func:`make_params`, which makes the weights the benchmark serves, in the
layout the program takes.

Model, per frame (NHWC, values in [0, 1]):

* the frame is put on the activation grid (round half to even, saturate);
* eight 3x3 convolutions at stride 1 and pad 1 with weights on the weight
  grid, each followed by the batch-norm affine, ReLU and the activation
  grid; a 2x2 max-pool after c1, c2 and c3; residual adds after r1b (onto
  the output of c1) and r2b (onto the output of c3);
* global average pooling to an 8*width feature;
* the EASY ensemble: the feature of the frame plus that of its mirror image.

The affine and the rounding onto the activation grid are exact, as in real
arithmetic (:func:`_levels`): a float32 affine rounds a product lying
within an ulp of a half-step to either side.  A convolution's output is
exact in float32 (its operands sit on small grids) and a whole multiple of
the step that the two grids give, so the code of every activation is the
number of grid levels whose start, as a multiple of that step, it reaches.
Those starts are worked out per channel on the host in float64, which
holds ``n * step * gamma + beta`` exactly for every whole ``n`` that a
convolution reaches.

Work (:func:`weight_matmuls`, :func:`macs_per_frame`): the benchmark's
readers count the operations of a served frame from these, found by the
configuration's ``model``, so that a configuration of another backbone
brings its counts in a reference file of its own.

Few-shot head: L2-normalised features, class means of the normalised
support features, L2-normalised again; a query's similarity to a class is
the cosine between its normalised feature and the normalised mean.

The control variants compute the same model one step lower in precision:

* ``"w4"``: weights on a 4-bit grid (1 sign bit, 3 fraction bits) in place
  of the configuration's weight grid, as int4 codes would hold them;
* ``"high"``: convolutions at ``HIGH`` precision (three bfloat16 passes)
  in place of ``HIGHEST``;
* ``"bf16"``: every convolution's output rounded to bfloat16 (its operands
  are exact in bfloat16 already).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def plan(width: int):
    """``(name, cin, cout, pool, residual)`` per conv; ``residual`` is
    ``"open"`` where a residual pair takes its input, ``"close"`` where the
    pair adds it back."""
    w = width
    return [("c0", 3, w, False, None), ("c1", w, 2 * w, True, None),
            ("r1a", 2 * w, 2 * w, False, "open"),
            ("r1b", 2 * w, 2 * w, False, "close"),
            ("c2", 2 * w, 4 * w, True, None), ("c3", 4 * w, 8 * w, True, None),
            ("r2a", 8 * w, 8 * w, False, "open"),
            ("r2b", 8 * w, 8 * w, False, "close")]


def weight_matmuls(cfg: Dict) -> List[Tuple[str, int, int, int]]:
    """Every weight matmul of one forward pass of one frame as ``(name, rows
    per frame, K, N)``: a 3x3 conv of :func:`plan` at stride 1 and pad 1 is
    an im2col matmul with one row per output pixel and ``K = 9 * cin``; its
    2x2 max-pool halves the side for the next conv."""
    out, side = [], int(cfg["img"])
    for name, cin, cout, pool, _ in plan(int(cfg["width"])):
        out.append((name, side * side, 9 * cin, cout))
        if pool:
            side //= 2
    return out


def macs_per_frame(cfg: Dict) -> int:
    """Every multiply-accumulate of one forward pass of one frame: those of
    the weight matmuls, since ResNet-9 multiplies no two activations."""
    return sum(m * k * n for _, m, k, n in weight_matmuls(cfg))


def key_for(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size, beyond 32 bits too."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def make_params(seed: int, cfg: Dict) -> Dict:
    """Untrained weights from ``seed``, made on the device in one jitted
    call: He-normal conv kernels (3, 3, cin, cout) and, as a trained
    deployment holds them, a batch-norm scale and shift per channel
    (scale uniform in [0.5, 2], shift uniform in [-0.5, 0.5]).  float32,
    the type the program's deploy takes them in."""
    width = int(cfg["width"])

    @jax.jit
    def make(key):
        p = {}
        for name, cin, cout, _, _ in plan(width):
            key, kw, kg, kb = jax.random.split(key, 4)
            std = float(np.sqrt(2.0 / (9 * cin)))
            p[name] = {"w": jax.random.normal(kw, (3, 3, cin, cout),
                                              jnp.float32) * std,
                       "gamma": jax.random.uniform(kg, (cout,), jnp.float32,
                                                   0.5, 2.0),
                       "beta": jax.random.uniform(kb, (cout,), jnp.float32,
                                                  -0.5, 0.5)}
        return p

    return make(key_for(seed))


def _grid(x, total_bits: int, frac_bits: int, signed: bool):
    """Round half to even onto a fixed-point grid, saturating."""
    scale = 2.0 ** -frac_bits
    lo = -(2 ** (total_bits - 1)) if signed else 0
    hi = 2 ** (total_bits - 1) - 1 if signed else 2 ** total_bits - 1
    return jnp.clip(jnp.round(x / scale), lo, hi) * scale


def _levels(gamma, beta, step: float, aq: Dict) -> np.ndarray:
    """(C, hi) float32: for each channel and each code ``q = 1 .. hi`` of
    the activation grid, the least whole ``n`` at which ``ReLU(gamma * n *
    step + beta)`` rounds (half to even) to ``q`` or above, in exact
    arithmetic.  With ``gamma > 0`` that is where the affine reaches the
    half-step ``(q - 1/2) * scale``, or, at the half-step itself, ``q`` is
    even.  float64 holds each ``n * step * gamma + beta`` exactly: ``n *
    step`` has at most 24 significant bits, ``gamma`` 24, and the sum's
    lowest bit is ``beta``'s."""
    g = np.asarray(gamma, np.float64)[:, None]
    b = np.asarray(beta, np.float64)[:, None]
    assert (g > 0).all(), "the batch-norm scale must be positive"
    hi = 2 ** (aq["total_bits"] - 1) - 1 if aq["signed"] else \
        2 ** aq["total_bits"] - 1
    q = np.arange(1, hi + 1, dtype=np.float64)
    half = (q - 0.5) * 2.0 ** -aq["frac_bits"]
    even = q % 2 == 0

    def reaches(n):
        y = n * step * g + b
        return (y > half) | ((y == half) & even)

    # the quotient rounds, so its ceiling may be one off either way
    n = np.ceil((half - b) / (g * step))
    n = np.where(reaches(n - 1), n - 1, n)
    n = np.where(reaches(n), n, n + 1)
    return np.clip(n, -2.0 ** 24, 2.0 ** 24).astype(np.float32)


def _act_levels(params, cfg: Dict, control: Optional[str]) -> Dict:
    """:func:`_levels` of every conv, for the weight grid of ``control``."""
    aq = cfg["quant"]["act"]
    step = 2.0 ** -(aq["frac_bits"] + _weight_grid(cfg, control)["frac_bits"])
    return {name: _levels(params[name]["gamma"], params[name]["beta"], step,
                          aq)
            for name, _, _, _, _ in plan(int(cfg["width"]))}


def _weight_grid(cfg: Dict, control: Optional[str]) -> Dict:
    if control == "w4":
        return {"total_bits": 4, "frac_bits": 3, "signed": True}
    return dict(cfg["quant"]["weight"])


def _conv(x, w, precision, bf16_out):
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)
    if bf16_out:
        # rounded as a bfloat16 output would be; a cast pair may be elided
        y = jax.lax.reduce_precision(y, exponent_bits=8, mantissa_bits=7)
    return y


def _forward(params, levels, x, cfg, control):
    wq = _weight_grid(cfg, control)
    aq = cfg["quant"]["act"]
    precision = jax.lax.Precision.HIGH if control == "high" else HIGHEST
    scale = 2.0 ** -aq["frac_bits"]
    step = 2.0 ** -(aq["frac_bits"] + wq["frac_bits"])
    h = _grid(x, aq["total_bits"], aq["frac_bits"], aq["signed"])
    skip = None
    for name, _, _, pool, residual in plan(int(cfg["width"])):
        p = params[name]
        w = _grid(p["w"], wq["total_bits"], wq["frac_bits"], wq["signed"])
        steps = _conv(h, w, precision, control == "bf16") / step
        # batch-norm affine, ReLU and the activation grid, exactly
        codes = jnp.sum(steps[..., None] >= levels[name], axis=-1)
        y = codes.astype(jnp.float32) * scale
        if pool:
            n, hh, ww, c = y.shape
            y = y.reshape(n, hh // 2, 2, ww // 2, 2, c).max(axis=(2, 4))
        if residual == "open":
            skip = h
        if residual == "close":
            y = y + skip
        h = y
    return jnp.mean(h, axis=(1, 2))


@functools.partial(jax.jit, static_argnames=("cfg_key", "control"))
def _features(params, levels, x, cfg_key, control):
    cfg = dict(cfg_key)
    cfg["quant"] = {k: dict(v) for k, v in cfg["quant"]}
    f = _forward(params, levels, x, cfg, control)
    if cfg["easy_augment"]:
        f = f + _forward(params, levels, x[:, :, ::-1], cfg, control)
    return f


def _freeze(cfg: Dict):
    q = tuple((k, tuple(sorted(v.items())))
              for k, v in sorted(cfg["quant"].items()))
    return (("width", int(cfg["width"])),
            ("easy_augment", bool(cfg["easy_augment"])), ("quant", q))


def features(params, x, cfg: Dict, control: Optional[str] = None,
             block: int = 64) -> np.ndarray:
    """(n, H, W, 3) frames -> (n, 8*width) features, in blocks of
    ``block`` frames so that the reference fits beside anything else."""
    x = np.asarray(x, np.float32)
    key = _freeze(cfg)
    levels = _act_levels(params, cfg, control)
    out = []
    for i in range(0, x.shape[0], block):
        chunk = x[i:i + block]
        n = chunk.shape[0]
        if n < block:
            chunk = np.concatenate(
                [chunk, np.zeros((block - n,) + chunk.shape[1:], np.float32)])
        out.append(np.asarray(_features(params, levels, jnp.asarray(chunk),
                                        key, control))[:n])
    return np.concatenate(out) if out else np.zeros((0, 8 * cfg["width"]))


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
