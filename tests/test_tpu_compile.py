"""The Pallas kernels and deployed graphs, compiled for a TPU v5e chip.

Nothing runs: each case is lowered and compiled for one chip of a described
``v5e:2x2`` topology, so what the chip's compiler refuses (a block that
breaks the (8, 128) tiling rule, a dot the MXU has no path for, more VMEM
than a kernel may hold) fails here, without a chip.  Results on the chip are
checked by ``chip_smoke.py``.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.attention import attn_full_pallas
from repro.kernels.gap import gap_pallas
from repro.kernels.mvau import mvau_int_pallas, mvau_pallas
from repro.kernels.qmatmul import qmatmul_pallas

I8, I32, F32, BF16 = jnp.int8, jnp.int32, jnp.float32, jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


# resnet9 at the paper's width 64 (configs/resnet9_paper.py), batch 1 at
# 32x32: c0 is the 3x3 stem (K = 27, M = 32*32), r2a the widest conv
# (K = 9*512, N = 512, M = 4*4 padded to a tile)
KERNELS = {
    "mvau_int-c0": (mvau_int_pallas,
                    [((1024, 27), I8), ((27, 64), I8), ((64, 15), I32)]),
    "mvau_int-r2a": (mvau_int_pallas,
                     [((128, 4608), I8), ((4608, 512), I8), ((512, 15), I32)]),
    "mvau_int-packed-int4": (partial(mvau_int_pallas, w_packed=True),
                             [((128, 4608), I8), ((4608, 256), I8),
                              ((512, 15), I32)]),
    "mvau_int-packed-int4-ragged": (partial(mvau_int_pallas, w_packed=True),
                                    [((6, 36), I8), ((36, 16), I8),
                                     ((32, 15), I32)]),
    # 8-bit activations: lm-tiny's fused MLP nodes threshold at 255 levels
    "mvau_int-L255": (mvau_int_pallas,
                      [((8, 256), I8), ((256, 64), I8), ((64, 255), I32)]),
    "mvau-f32": (mvau_pallas,
                 [((1024, 27), F32), ((27, 64), F32), ((64, 15), F32)]),
    "mvau-f32-L255": (mvau_pallas,
                      [((8, 256), F32), ((256, 64), F32), ((64, 255), F32)]),
    # ViT-S/16's attention at a 64-frame bucket: 197 tokens padded to 200,
    # 6 heads of 64 taken in 128-lane pairs, codes of 5 fraction bits
    "attn_full-vit-s16": (partial(attn_full_pallas, heads=6, frac_bits=5),
                          [((64, 197, 1152), F32)]),
    "gap-int-batch8": (gap_pallas, [((8, 4, 4, 512), I32)]),
    "gap-f32-ragged": (gap_pallas, [((3, 5, 7, 24), F32)]),
    "qmatmul-int4": (partial(qmatmul_pallas, bits=4),
                     [((8, 256), BF16), ((256, 128), I8), ((256,), F32)]),
    "qmatmul-int8": (partial(qmatmul_pallas, bits=8),
                     [((8, 256), BF16), ((256, 256), I8), ((256,), F32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]
    compiled = _compile(fn, shapes, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("datapath", ["int", "f32"])
def test_resnet9_paper_width_compiles_for_v5e(datapath, one_chip):
    """The paper's deployment point (width 64, w6a4) lowered with the chip's
    dispatch — every MVAU and the GAP a compiled Pallas kernel — at a
    serving batch of 16."""
    from repro.configs import resnet9_paper
    from repro.core.deploy import lower_graph
    from repro.fsl.pipeline import FSLPipeline

    pipe = FSLPipeline(width=64, qcfg=resnet9_paper.QUANT)
    params = pipe._hooks().init_params(jax.random.PRNGKey(0), 64)
    dm = pipe.deploy(params, datapath=datapath).deployed_model
    compiled = _compile(lower_graph(dm.graph, interpret=False),
                        [((16, 32, 32, 3), F32)], one_chip)
    # 8 MVAUs + the GAP
    assert compiled.as_text().count("tpu_custom_call") >= 9


def test_im2col_compiles_for_v5e_without_loops(one_chip):
    """resnet9's c1 patches at bucket 64, cast to int8 as the int MVAU's
    operand: static slices compile to fusions. Index-array gathers compile
    to a ``while`` loop of dynamic slices here."""
    from repro.core.graph import im2col

    compiled = _compile(lambda x: im2col(x).astype(I8),
                        [((64, 32, 32, 64), I32)], one_chip)
    hlo = compiled.as_text()
    assert "while" not in hlo and "fusion" in hlo


def test_lm_tiny_decode_step_compiles_for_v5e(one_chip):
    """lm-tiny's int decode step, with its two 255-level fused MLP MVAUs on
    the Pallas kernel and the int8 matmuls on the MXU."""
    import numpy as np

    import repro.configs.lm_tiny  # noqa: F401  (registers the arch)
    from repro.core.deploy import lower_graph
    from repro.kernels.ops import kernel_dispatch
    from repro.models import lm
    from repro.models.common import get_config
    from repro.serve.decode import build_decode_artifact

    cfg = get_config("lm-tiny")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    art = build_decode_artifact(params, cfg, datapath="int", capacities=(16,),
                                verify=False, interpret=False)
    g = art.dm.graph
    fused = [n for n in g.nodes if n.op == "mvau_int"
             and kernel_dispatch(n, emulated=False) == "fused-pallas"]
    assert len(fused) == 2
    feeds = lm.example_decode_feeds(cfg, batch=2, capacity=16)
    shapes = [(np.shape(feeds[n]), np.asarray(feeds[n]).dtype)
              for n in art.dm.input_names]
    compiled = _compile(lower_graph(g, interpret=False), shapes, one_chip)
    assert compiled.as_text().count("tpu_custom_call") >= 2


def test_vit_s16_layer_compiles_for_v5e(one_chip):
    """One ViT-S/16 layer at its published widths on 64x64 frames (17 tokens), int
    datapath, lowered with the chip's dispatch at a bucket of 8: every
    linear an int8 dot, the attention the Pallas kernel."""
    from repro.core.deploy import lower_graph
    from repro.core.quant import FixedPointSpec, QuantConfig
    from repro.fsl.pipeline import FSLPipeline
    from repro.models import vit

    qcfg = QuantConfig(weight=FixedPointSpec(8, 8, True),
                       act=FixedPointSpec(8, 5, True))
    params = jax.jit(vit.init_params)(jax.random.PRNGKey(0))
    params = dict(params, blocks=params["blocks"][:1],
                  pos=params["pos"][:17])          # one layer, 4x4 patches
    pipe = FSLPipeline(arch="vit-s16", width=384, qcfg=qcfg,
                       easy_augment=False)
    dm = pipe.deploy(params, datapath="int").deployed_model
    compiled = _compile(lower_graph(dm.graph, interpret=False),
                        [((8, 64, 64, 3), F32)], one_chip)
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 1
    assert "while" not in hlo
    # patch embedding, qkv, projection, fc1, fc2: int32-accumulated dots
    int_dots = [ln for ln in hlo.splitlines()
                if "= s32[" in ln and ("convolution(" in ln or " dot(" in ln)]
    assert len(int_dots) == 5
