"""CPU rehearsal of the ViT-S/16 cell at a tiny size.

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests

The configuration ``vit-s16-int`` cut to width 64, 2 heads, 2 layers and
32x32 frames of 8x8 patches, served through the harness as ``run.py``
serves it: the program answers correctly, the control (its float values
between quantizers in bfloat16) fails the comparison, and the reference counts the
published model's work.
"""

import json
import os
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import devtrace  # noqa: E402
import harness  # noqa: E402

CELL = "vit-s16-int.cams128"
TINY = {"width": 64, "heads": 2, "depth": 2, "img": 32, "patch": 8,
        "mlp": 256}


def tiny_vit(clients: int = 8) -> "harness.Cell":
    import run  # noqa: F401  (sets up the compile cache like the command)

    run.configure_jax()
    cell = harness.resolve(harness.load_benchmark(), CELL)
    cell.cfg.update(TINY)
    cell.cfg["engine"] = dict(cell.cfg["engine"], max_batch=8,
                              buckets=[1, 2, 4, 8])
    cell.traffic = dict(cell.traffic, pool_frames=32, clients=clients)
    return cell


@pytest.mark.parametrize("config", ["vit-s16-int"])
def test_control_fails_the_comparison(config):
    cell = tiny_vit()
    assert cell.config == config
    out = harness.run_cell(cell, 2**33 + 21, 0.5, False, time.perf_counter())
    assert out.result["correct"] is True and out.result["failed"] == 0
    ctl = harness.control_readings(cell, out.evidence, cell.cfg["control"])
    limits = cell.cfg["limits"]
    assert any(ctl[k] > limits[k] for k in limits), ctl


def test_served_through_the_recipe_with_every_linear_on_int8():
    """The cell deploys ``vit-s16`` on the integer datapath; with the chip's
    dispatch every linear is an int8 matmul and every attention node the
    Pallas kernel."""
    from repro.kernels.ops import kernel_dispatch

    served = []
    cell = tiny_vit()
    out = harness.run_cell(
        cell, 2**33 + 31, 0.5, False, time.perf_counter(),
        fault=lambda s: served.append(s.recorder.deployed_model))
    assert out.result["correct"] is True
    (dm,) = served
    assert dm.recipe_name == "vit-s16" and dm.datapath == "int"
    kinds = {}
    for n in dm.graph.nodes:
        kinds.setdefault(n.op, set()).add(kernel_dispatch(n, emulated=False))
    assert kinds["matmul_int"] == {"int8-dot"}
    assert kinds["attn_full"] == {"pallas"}
    assert "matmul" not in kinds
    assert len([n for n in dm.graph.nodes if n.op == "matmul_int"]) == 9


def test_work_at_the_published_widths():
    cfg = harness.resolve(harness.load_benchmark(), CELL).cfg
    model = harness.model_module(cfg)
    assert model.macs_per_frame(cfg) == 4_598_498_304
    mm = model.weight_matmuls(cfg)
    assert mm[0] == ("patch", 196, 768, 384) and len(mm) == 1 + 4 * 12
    assert sum(m * k * n for _, m, k, n in mm[:1]) == 57_802_752
    flops, nbytes = model.attn_work(cfg)
    assert flops // 2 == 29_805_312                     # QK^T and PV MACs
    assert nbytes == 4 * 197 * 4 * 384


def _kernel_trace(starts, span):
    ops = [("attn_full_pallas.3", s, s + 1e-3) for s in starts]
    ops.append(("fusion.7", span[0] + 1e-4, span[0] + 2e-4))
    return devtrace.DeviceTrace({"/device:TPU:0": ops})


def test_attn_roofline_counts_whole_calls_only():
    cell = tiny_vit()
    cfg = dict(cell.cfg, depth=3)
    cell.cfg = cfg
    spans = [{"name": "serve.batch", "t0": 1.0, "dur_ms": 100.0,
              "attrs": {"n_real": 4, "bucket": 4}},
             {"name": "serve.batch", "t0": 2.0, "dur_ms": 100.0,
              "attrs": {"n_real": 8, "bucket": 8}}]
    # the first call's three kernels are seen whole, the second's only two
    dt = _kernel_trace([1.01, 1.03, 1.05, 2.01, 2.03], (1.0, 1.1))
    run = harness.TraceRun(cell, spans, 0.0, 10.0, dt, "TPU v5 lite",
                           {"frames_per_s": 1.0})
    flops, nbytes = run.model().attn_work(cfg)
    pk = run.peaks()
    bound = 3 * 4 * max(flops / pk["bf16_flops"],
                        nbytes / pk["hbm_bytes_per_s"])
    got = harness.reader("attn_roofline")(run)
    assert got == pytest.approx(100.0 * bound / 3e-3)
    quiet = harness.TraceRun(cell, spans, 0.0, 10.0,
                             devtrace.DeviceTrace({}), "TPU v5 lite",
                             {"frames_per_s": 1.0})
    assert harness.reader("attn_roofline")(quiet) is None


def test_config_states_its_cut():
    with open(os.path.join(BENCH, "configs", "vit-s16-int.json")) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == ["qat_steps"]
    assert (cfg["width"], cfg["heads"], cfg["depth"], cfg["mlp"],
            cfg["patch"], cfg["img"]) == (384, 6, 12, 1536, 16, 224)
