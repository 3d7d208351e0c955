"""DINO ViT-S/16 as a few-shot backbone: the QAT model, its deployed graph on
both datapaths and the serving engine against the plain reference
(``bench/configs/vit-s16.py``), at a small size on seeded random weights;
the bidirectional attention kernel against its oracle; the ops the model
added to the graph IR."""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import im2col
from repro.core.quant import FixedPointSpec, QuantConfig, fake_quant

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL = {"width": 64, "heads": 2, "depth": 2, "img": 32, "patch": 8,
         "mlp": 256}
PUBLISHED = {"width": 384, "heads": 6, "depth": 12, "img": 224, "patch": 16,
             "mlp": 1536}
GRIDS = {"weight": {"total_bits": 8, "frac_bits": 8, "signed": True},
         "act": {"total_bits": 8, "frac_bits": 5, "signed": True}}
QCFG = QuantConfig(weight=FixedPointSpec(8, 8, True),
                   act=FixedPointSpec(8, 5, True))


def _reference():
    path = os.path.join(ROOT, "bench", "configs", "vit-s16.py")
    spec = importlib.util.spec_from_file_location("vit_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _reference()


@pytest.fixture(scope="module")
def small(ref):
    cfg = dict(SMALL, quant=GRIDS)
    params = ref.make_params(2**33 + 7, cfg)
    x = jax.random.uniform(jax.random.PRNGKey(3), (6, 32, 32, 3))
    return cfg, params, np.asarray(x)


@pytest.fixture(scope="module")
def qat(small):
    """The QAT forward's features of the small frames, in one jitted call."""
    from repro.models import vit

    _, params, x = small
    fwd = jax.jit(lambda p, x: vit.forward(p, x, QCFG, 64))
    return np.asarray(fwd(params, jnp.asarray(x)))


@pytest.fixture(scope="module")
def deployed(small):
    from repro.fsl.pipeline import FSLPipeline

    _, params, _ = small
    pipe = FSLPipeline(arch="vit-s16", width=64, qcfg=QCFG,
                       easy_augment=False)
    return {dp: pipe.deploy(params, datapath=dp) for dp in ("f32", "int")}


# -- the model against the plain reference ----------------------------------
def test_qat_forward_matches_the_plain_reference(ref, small, qat):
    """Linears, attention, LayerNorm and GELU are exact integer arithmetic,
    and each float value is one correctly rounded operation: the features
    agree bit for bit.  The reference one step lower in precision departs
    by far more."""
    cfg, params, x = small
    want = ref.features(params, x, cfg)
    got = qat
    assert got.shape == (6, 64)
    np.testing.assert_array_equal(got, want)
    ctl = ref.features(params, x, cfg, "bf16")
    assert np.abs(ctl - want).max() > 1e-2 * np.abs(want).max()


@pytest.mark.parametrize("datapath", ["f32", "int"])
def test_deployed_graph_is_the_qat_model(small, qat, deployed, datapath):
    """Every linear, the attention, LayerNorm and GELU are exact on both
    datapaths: bit for bit."""
    _, _, x = small
    got = np.asarray(deployed[datapath](jnp.asarray(x)))
    np.testing.assert_array_equal(got, qat)


def test_int_lowering_keeps_every_linear_on_int8_codes(deployed):
    """Each linear lowers to ``matmul_int`` (int8 on the chip), its
    accumulator dequantized exactly before the float bias add."""
    from repro.kernels.ops import kernel_dispatch

    g = deployed["int"].deployed_model.graph
    mms = [n for n in g.nodes if n.op == "matmul_int"]
    assert len(mms) == 1 + 4 * SMALL["depth"]
    assert not any(n.op in ("matmul", "multithreshold") for n in g.nodes)
    for n in mms:
        assert kernel_dispatch(n, emulated=False) == "int8-dot"
        (deq,) = g.consumers(n.outputs[0])
        (add,) = g.consumers(deq.outputs[0])
        assert deq.op == "dequantize" and add.op == "add"
        assert add.inputs[1] in g.initializers            # the bias
    attn = [n for n in g.nodes if n.op == "attn_full"]
    assert len(attn) == SMALL["depth"]
    assert {kernel_dispatch(n, emulated=False) for n in attn} == {"pallas"}
    assert {kernel_dispatch(n, emulated=True) for n in attn} == {"ref-oracle"}


def test_golden_check_runs_the_attention_kernel(small):
    """``compile`` with a golden input checks each attention node's Pallas
    kernel (interpreted here) against the oracle, and refuses one that
    departs in a single output by one grid step."""
    import repro
    from repro.core.graph import GraphBuildError
    from repro.kernels import ops as kops

    _, params, x = small
    xq = np.asarray(fake_quant(jnp.asarray(x[:1]), QCFG.act))
    dm = repro.compile(params, QCFG, recipe="vit-s16", datapath="int",
                       sample_input=xq)
    assert dm.recipe_name == "vit-s16"
    real = kops.attn_full
    try:
        kops.attn_full = lambda qkv, heads, frac, interpret=None: \
            real(qkv, heads, frac, interpret).at[0, 3, 5].add(2.0 ** -21)
        with pytest.raises(GraphBuildError, match="departs from its oracle"):
            repro.compile(params, QCFG, recipe="vit-s16", datapath="int",
                          sample_input=xq)
    finally:
        kops.attn_full = real


def test_served_by_the_engine_without_retraces(ref, small, deployed):
    """The int artifact behind ServeEngine: each warmed bucket traces every
    attention layer once, load traces nothing, and the answers are the
    plain reference's classes."""
    from repro.serve import ArtifactRegistry, ServeEngine

    cfg, params, x = small
    reg = ArtifactRegistry()
    reg.register("vit", deployed["int"], default=True)
    with ServeEngine(reg, max_batch=8, buckets=(2, 8),
                     batch_wait_ms=1.0) as eng:
        key = ServeEngine.ATTN_TRACES
        before = eng.trace_counts()[key]
        base = eng.warmup(img=32)
        assert base[key] - before == 2 * SMALL["depth"]    # two buckets
        for c in range(2):
            eng.submit_register(c, x[3 * c:3 * c + 2]).result(60)
        res = eng.submit_classify(x).result(60)
        after = eng.trace_counts()
        assert after[key] == base[key]
        assert {k: v for k, v in after.items() if k != eng.HEAD_TRACES} == \
            {k: v for k, v in base.items() if k != eng.HEAD_TRACES}
    support = ref.features(params, np.concatenate([x[0:2], x[3:5]]), cfg)
    protos = ref.prototypes(support, np.asarray([0, 0, 1, 1]), 2)
    sims = ref.sims(ref.features(params, x, cfg), protos)
    assert np.abs(np.asarray(res.sims) - sims).max() < 1e-3
    clear = np.abs(sims[:, 0] - sims[:, 1]) > 2e-3
    assert (np.asarray(res.class_ids)[clear] == sims.argmax(1)[clear]).all()


def test_macs_per_frame_at_the_published_widths(ref):
    cfg = dict(PUBLISHED, quant=GRIDS)
    assert ref.macs_per_frame(cfg) == 4_598_498_304
    per_layer = sum(m * k * n for nm, m, k, n in ref.weight_matmuls(cfg)
                    if nm.startswith("l0."))
    assert per_layer + ref.attn_work(cfg)[0] // 2 == 378_391_296


def test_structure_is_read_from_the_parameter_shapes(small):
    from repro.models import vit

    _, params, _ = small
    d = vit.dims(params)
    assert (d.width, d.heads, d.depth, d.patch, d.img, d.mlp) == \
        (64, 2, 2, 8, 32, 256)
    full = jax.eval_shape(lambda k: vit.init_params(k), jax.random.PRNGKey(0))
    d = vit.dims(full)
    assert (d.width, d.heads, d.depth, d.patch, d.img, d.mlp) == \
        (384, 6, 12, 16, 224, 1536)


# -- the attention kernel -----------------------------------------------------
def _codes_on_grid(key, shape, scale=1.5):
    """Random q|k|v on the 8-bit activation grid of 5 fraction bits."""
    x = jax.random.normal(key, shape) * scale
    return jnp.clip(jnp.round(x * 32), -128, 127) / 32


@pytest.mark.parametrize("seq,width,heads", [
    (17, 64, 2),        # S padded to 24 sublanes: padded keys masked
    (16, 64, 2),        # no padding
    (20, 256, 4),       # 128-lane groups of two heads, padded
    (24, 256, 4),
    (197, 384, 6)])     # ViT-S/16: 6 heads of 64, padded to 200
def test_attention_kernel_matches_its_oracle(seq, width, heads):
    """Integer throughout, so the kernel gives its oracle's bits."""
    from repro.kernels import ref
    from repro.kernels.attention import attn_full_pallas

    qkv = _codes_on_grid(jax.random.PRNGKey(seq), (2, seq, 3 * width))
    got = attn_full_pallas(qkv, heads=heads, frac_bits=5, interpret=True)
    want = ref.attn_full(qkv, heads, 5)
    assert got.shape == (2, seq, width)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_causal_prefill_is_unchanged_and_full_attention_sees_every_key():
    """The decode path's causal attention is the float softmax it was; the
    ViT's integer attention is that softmax without the mask, on codes,
    within I-BERT's exp (0.31%) and the 2**-16 weight grid."""
    from repro.kernels import ref

    q, k, v = (np.asarray(_codes_on_grid(jax.random.PRNGKey(i), (2, 5, 8)))
               for i in range(3))

    def naive(causal):
        out = np.zeros((2, 5, 8))
        for b in range(2):
            for h in range(2):
                sl = slice(4 * h, 4 * h + 4)
                for t in range(5):
                    keys = range(t + 1) if causal else range(5)
                    s = np.array([np.dot(q[b, t, sl], k[b, j, sl])
                                  for j in keys]) / 2.0
                    w = np.exp(s - s.max())
                    w /= w.sum()
                    out[b, t, sl] = sum(wj * v[b, j, sl]
                                        for wj, j in zip(w, keys))
        return out

    np.testing.assert_allclose(ref.attn_prefill(q, k, v, 2), naive(True),
                               rtol=1e-5, atol=1e-6)
    full = np.asarray(ref.attn_full(jnp.concatenate([q, k, v], -1), 2, 5))
    assert np.abs(full - naive(False)).max() < 0.01 * np.abs(v).max()
    assert np.abs(full - naive(True)).max() > 0.1 * np.abs(v).max()


def test_integer_softmax_constants():
    """ViT-S/16's scores step 2**-13: ln 2, I-BERT's B and C on that grid,
    and the shift that keeps each exp term below 2**14."""
    from repro.kernels import ref

    q_ln2, q_b, q_c, shift, n_max = ref.iexp_consts(5, 64)
    assert (q_ln2, q_b) == (5678, 11083)
    assert q_b * q_b + q_c < 2 ** 31
    assert (q_b * q_b + q_c) >> shift < 2 ** ref.EXP_BITS
    assert n_max == (31 - shift) * q_ln2
    with pytest.raises(ValueError, match="no int32 softmax"):
        ref.iexp_consts(8, 64)


# -- the graph IR ---------------------------------------------------------------
@pytest.mark.parametrize("side,k", [(32, 8), (224, 16), (20, 8)])
def test_im2col_patches_are_the_window_slices(side, k):
    """k == stride, no padding: the reshape path gives the columns the
    k·k-slice path gives, in (kh, kw, c) order (a side that k does not
    divide drops the remainder)."""
    x = np.random.default_rng(side).normal(size=(2, side, side, 3)).astype(np.float32)
    got = np.asarray(im2col(jnp.asarray(x), k, k, 0))
    o = side // k
    want = np.concatenate([x[:, i:i + (o - 1) * k + 1:k, j:j + (o - 1) * k + 1:k]
                           for i in range(k) for j in range(k)], axis=-1)
    np.testing.assert_array_equal(got, want)


def test_im2col_3x3_keeps_its_slice_path():
    x = np.random.default_rng(1).normal(size=(2, 6, 6, 4)).astype(np.float32)
    xp = np.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    want = np.concatenate([xp[:, i:i + 6, j:j + 6] for i in range(3)
                           for j in range(3)], axis=-1)
    np.testing.assert_array_equal(np.asarray(im2col(jnp.asarray(x))), want)


def test_vit_ops_have_float_datatype_rules():
    from repro.core.datatypes import DATATYPE_RULES

    for op in ("layernorm", "igelu", "attn_full", "vit_tokens",
               "cls_readout"):
        assert DATATYPE_RULES[op](None, [FixedPointSpec(8, 5, True)],
                                  None) is None


# -- integer LayerNorm and GELU ----------------------------------------------
def test_isqrt_is_the_floor_square_root():
    from repro.core.graph import isqrt

    rng = np.random.default_rng(0)
    v = np.concatenate([np.arange(1, 1 << 16),
                        rng.integers(1, 2 ** 31 - 1, 1 << 16),
                        [2 ** 30, 2 ** 31 - 1]]).astype(np.int32)
    r = np.asarray(jax.jit(isqrt)(jnp.asarray(v))).astype(np.int64)
    assert ((r * r <= v) & ((r + 1) ** 2 > v)).all()


def _rows(n=384):
    """Residual-stream rows: spreads from 0.01 to 30, offsets, a constant
    row, one outlier, values past the input codes' range."""
    rng = np.random.default_rng(5)
    x = (rng.normal(size=(300, n)) * rng.uniform(0.01, 30, (300, 1))
         + rng.normal(size=(300, 1)) * 3)
    x[0] = 0.7
    x[1, 7] = 120.0
    x[2] = np.linspace(-2000.0, 2000.0, n)
    return x.astype(np.float32)


@pytest.mark.parametrize("out_frac", [5, 13])
def test_integer_layernorm_is_the_references(ref, out_frac):
    """The program's LayerNorm gives the plain reference's bits on every
    row, lies on its output grid, and within 0.004 + one output step of
    float LayerNorm on rows of ordinary spread."""
    from repro.core.graph import layernorm

    x = _rows()
    rng = np.random.default_rng(6)
    g = rng.uniform(0.5, 1.5, 384).astype(np.float32)
    b = rng.uniform(-0.25, 0.25, 384).astype(np.float32)
    got = np.asarray(jax.jit(lambda x: layernorm(x, g, b, 1e-6, 13,
                                                 out_frac))(x))
    want = np.asarray(ref._layernorm(jnp.asarray(x), {"g": g, "b": b}, 13,
                                     out_frac))
    np.testing.assert_array_equal(got, want)
    assert (got * 2.0 ** out_frac == np.round(got * 2.0 ** out_frac)).all()
    xf = x[3:].astype(np.float64)
    xc = xf - xf.mean(-1, keepdims=True)
    fl = xc / np.sqrt((xc * xc).mean(-1, keepdims=True) + 1e-6) * g + b
    spread = x[3:].std(-1) > 0.1
    assert np.abs(got[3:][spread] - fl[spread]).max() < \
        0.004 + 2.0 ** -out_frac


def test_integer_layernorm_bits_do_not_depend_on_the_program():
    """Integer inside: the same bits jitted whole, jitted on a transposed
    copy, and op by op without jit."""
    from repro.core.graph import layernorm

    x = _rows()[:64]
    g, b = np.full(384, 1.25, np.float32), np.full(384, 0.125, np.float32)

    def ln(x):
        return layernorm(x, g, b, 1e-6, 13, 5)

    whole = np.asarray(jax.jit(ln)(x))
    moved = np.asarray(jax.jit(lambda t: ln(t.T))(x.T))
    with jax.disable_jit():
        eager = np.asarray(ln(jnp.asarray(x)))
    np.testing.assert_array_equal(whole, moved)
    np.testing.assert_array_equal(whole, eager)


def test_integer_gelu_is_the_references_and_near_gelu(ref):
    """I-BERT's GELU: the plain reference's bits; within 0.022 of the exact
    (erf) GELU before its output is rounded, and the identity and zero far
    out."""
    from repro.core.graph import igelu

    x = jnp.linspace(-12.0, 12.0, 48001)
    got = np.asarray(jax.jit(lambda x: igelu(x, 8, 5))(x))
    np.testing.assert_array_equal(got, np.asarray(ref._gelu(x, 5)))
    assert (got * 32 == np.round(got * 32)).all()
    fine = np.asarray(igelu(x, 8, 17))
    exact = np.asarray(jax.nn.gelu(x, approximate=False))
    assert np.abs(fine - exact).max() < 0.022
    far = np.asarray(igelu(jnp.asarray([-50.0, 50.0]), 8, 5))
    np.testing.assert_array_equal(far, [0.0, 50.0])
    with pytest.raises(ValueError, match="no int32 GELU"):
        igelu(x, 10, 5)
