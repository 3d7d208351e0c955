"""Paper Table III: implementation/latency comparison.

Paper: Tensil 16-bit (35.9 ms) vs FINN 6/4-bit (16.3 ms, 61.5 fps) — the
bit-width reduction converts to ~2.2× throughput because the deployment is
resource/bytes-bound, not FLOP-bound.

TPU analogue, reported two ways:
  (a) MEASURED on this host: backbone inference wall-clock, fp32 graph vs
      streamlined quantized HW graph (CPU timings — relative, not absolute);
  (b) ROOFLINE-DERIVED (TPU v5e): HBM-byte model of the backbone at w16a16
      vs w6a4 storage — the honest fleet-scale counterpart, matching the
      dry-run §Perf decode result (bf16 vs w4+int8-cache = 1.85×).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import build
from repro.core.graph import execute
from repro.core.quant import FixedPointSpec, QuantConfig
from repro.models import resnet9
from repro.obs.costmodel import DEVICE_PEAKS

WIDTH = 16
HBM_BW = DEVICE_PEAKS["TPU v5 lite"]["hbm_bw"]


def _bench(fn, x, iters=5):
    fn(x)  # compile
    t0 = time.time()
    for _ in range(iters):
        out = fn(x)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters


def roofline_latency_model(width: int, qcfg, img: int = 32,
                           batch: int = 1) -> float:
    """HBM-bytes lower bound for one backbone pass on TPU v5e.

    weights at their storage width + activations at act width, each streamed
    once — the FINN 'weights live on-chip' point maps to weights being read
    once per frame from HBM at their *storage* width.
    """
    from repro.core.quant import storage_bytes_per_element
    wb = storage_bytes_per_element(qcfg.weight if qcfg else None, fp_bytes=4)
    ab = storage_bytes_per_element(qcfg.act if qcfg else None, fp_bytes=4)
    total = 0.0
    hw = img * img
    for blk in resnet9.plan(width):
        total += 9 * blk["cin"] * blk["cout"] * wb          # conv weights
        total += batch * hw * blk["cout"] * ab * 2          # act out+in
        if blk.get("pool"):
            hw //= 4
    return total / HBM_BW


def run(quick: bool = False):
    key = jax.random.PRNGKey(0)
    params = resnet9.init_params(key, WIDTH)
    x = jax.random.uniform(jax.random.PRNGKey(1), (8, 32, 32, 3))
    q16 = QuantConfig.paper_w16a16()
    q64 = QuantConfig.paper_w6a4()

    # (a) measured: fp32 model vs streamlined quantized graph interpreter
    fp_fn = jax.jit(lambda x: resnet9.forward(params, x, None, WIDTH))
    t_fp = _bench(fp_fn, x)

    g = resnet9.export_graph(params, q64, width=WIDTH)
    hw = build.build_dataflow(g, build.RESNET9_BUILD_STEPS)
    from repro.core.quant import fake_quant
    xq = fake_quant(x, q64.act)
    hw_fn = jax.jit(lambda x: execute(hw, {"x": x})[0])
    t_hw = _bench(hw_fn, xq)

    # (b) roofline (TPU v5e) — bytes-bound latency at each bit-width
    r16 = roofline_latency_model(WIDTH, q16)
    r64 = roofline_latency_model(WIDTH, q64)

    print(f"table3,measured_fp32_ms,{t_fp*1e3:.2f}")
    print(f"table3,measured_w6a4_hwgraph_ms,{t_hw*1e3:.2f}")
    print(f"table3,roofline_v5e_w16a16_us,{r16*1e6:.2f}")
    print(f"table3,roofline_v5e_w6a4_us,{r64*1e6:.2f}")
    print(f"table3,roofline_speedup,{r16/r64:.2f}")
    return {"speedup_roofline": r16 / r64}


if __name__ == "__main__":
    run()
