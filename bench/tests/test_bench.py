"""CPU rehearsal of the benchmark harness at a tiny width.

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests

Off the chip the deployed graph runs its kernels' XLA oracles (the Pallas
kernels' interpret-mode equivalents), so these tests check the harness's
logic, counts and comparisons, never a time.  They drive the harness's own
functions, skipping only its look for a chip.
"""

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import devtrace  # noqa: E402
import harness  # noqa: E402
import peaks  # noqa: E402
import stats  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data")
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def tiny(name: str, clients: int = 8, root: str = ROOT) -> "harness.Cell":
    """The cell ``<config>.<traffic>`` of the checkout at ``root``, at width
    4 with few buckets and callers (its metrics those of its BENCHMARK.json
    where it is listed)."""
    import run  # noqa: F401  (sets up the compile cache like the command)

    run.configure_jax()
    config, traffic = name.split(".")
    cell = harness.make_cell(harness.load_benchmark(root), config, traffic,
                             root=root)
    cell.cfg["width"] = 4
    cell.cfg["engine"] = dict(cell.cfg["engine"], max_batch=8,
                              buckets=[1, 2, 4, 8])
    cell.traffic = dict(cell.traffic, pool_frames=32)
    if cell.traffic["loop"] == "closed":
        cell.traffic["clients"] = clients
    else:
        cell.traffic["rate"] = 100.0
    return cell


def run_tiny(name, fault=None, clients=8, seconds=0.5, trace=False):
    return harness.run_cell(tiny(name, clients), 2**33 + 17, seconds, trace,
                            time.perf_counter(), fault=fault)


# -- the result line ---------------------------------------------------------
@pytest.mark.parametrize("name", ["resnet9-w6a4-int.cams128",
                                  "resnet9-w6a4-f32.cams128"])
def test_result_line_has_the_contract_keys(name, capsys):
    out = run_tiny(name)
    harness.emit(out)
    lines = capsys.readouterr()
    last = json.loads(lines.out.strip().splitlines()[-1])
    assert list(last) == RESULT_KEYS
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    cell = tiny(name)
    assert set(last["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in cell.end_to_end:
        assert last["metrics"][m["name"]]["unit"] == m["unit"]
        assert last["metrics"][m["name"]]["value"] > 0
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert set(last["checks"]) == set(cell.cfg["limits"])
    err = lines.err.strip().splitlines()
    assert err[-1] == "correct: True"
    assert all(line.startswith("check ") for line in err[-4:-1])
    assert any("compiles 0" in line for line in lines.out.splitlines())


# -- failures and faults -----------------------------------------------------
def test_refused_request_counts_as_failed():
    from repro.serve import ServeOverload

    def refuse_every_third(system):
        submit, n = system.submit, [0]

        def flaky(x):
            n[0] += 1
            if n[0] % 3 == 0:
                raise ServeOverload("test: refused")
            return submit(x)
        system.submit = flaky

    out = run_tiny("resnet9-w6a4-int.poisson", fault=refuse_every_third)
    assert out.result["attempted"] == 50
    assert out.result["failed"] == 16
    assert out.result["correct"] is True     # refused, not wrong


def _break_answers(system):
    store = system.registry.get(system.ARTIFACT).store
    classify = store.classify

    def altered(q):
        ids, sims = classify(q)
        return [(int(i) + 1) % 5 for i in ids], sims[:, ::-1]
    store.classify = altered


def _half_batch(system):
    rec = system.recorder
    feats = rec._feats

    def half(x):
        out = np.asarray(feats(x)).copy()
        out[len(out) // 2:] = out[0]
        return out
    half.trace_count = feats.trace_count
    half.warmup = feats.warmup
    rec._feats = half


@pytest.mark.parametrize("fault", [_break_answers, _half_batch],
                         ids=["answer_altered", "half_batch_left_out"])
def test_broken_timed_path_is_not_correct(fault):
    out = run_tiny("resnet9-w6a4-int.cams128", fault=fault)
    assert out.result["correct"] is False
    assert out.result["failed"] > 0


def _redeploy(system, cfg, change):
    """The served backbone deployed again from ``change(gamma, beta)`` of
    every layer's batch norm: a fault in how the program folds it."""
    from repro.fsl.pipeline import FSLPipeline

    rec = system.recorder
    params = {k: dict(v) for k, v in rec._feats.params.items()}
    for p in params.values():
        p["gamma"], p["beta"] = change(p["gamma"], p["beta"])
    pipe = FSLPipeline(width=int(cfg["width"]),
                       qcfg=harness.quant_config(cfg),
                       easy_augment=bool(cfg["easy_augment"]))
    feats = pipe.deploy(params, datapath=cfg["datapath"])
    feats.warmup(system.engine.buckets, img=int(cfg["img"]))
    rec._feats = feats


@pytest.mark.parametrize("change", [lambda g, b: (g, -b),
                                    lambda g, b: (1.0 / g, b)],
                         ids=["shift_negated", "scale_inverted"])
def test_batch_norm_folded_wrong_is_not_correct(change):
    cell = tiny("resnet9-w6a4-int.cams128")
    out = harness.run_cell(cell, 2**33 + 19, 0.5, False, time.perf_counter(),
                           fault=lambda s: _redeploy(s, cell.cfg, change))
    assert out.result["correct"] is False
    assert out.evidence["readings"]["feat_gap"] > cell.cfg["limits"]["feat_gap"]


def test_compile_counter_reports_an_unwarmed_shape(capsys):
    def unwarmed_bucket(system):
        # batches of 3 rows now pad to 3, a shape nothing compiled
        system.engine.buckets = (1, 3, 8)

    out = run_tiny("resnet9-w6a4-int.cams128", fault=unwarmed_bucket,
                   clients=3)
    line = [n for n in out.notes if n.startswith("inside the window")][0]
    assert "compiles 0 " not in line
    n = int(line.split("compiles ")[1].split()[0])
    assert n >= 1


@pytest.mark.parametrize("config", ["resnet9-w6a4-int", "resnet9-w6a4-f32"])
def test_control_fails_the_comparison(config):
    cell = tiny(f"{config}.cams128")
    out = harness.run_cell(cell, 2**33 + 21, 0.5, False, time.perf_counter())
    assert out.result["correct"] is True
    ctl = harness.control_readings(cell, out.evidence, cell.cfg["control"])
    limits = cell.cfg["limits"]
    assert any(ctl[k] > limits[k] for k in limits), ctl


# -- the reference's batch-norm affine and rounding ----------------------------
def _exact_code(n: int, step: float, gamma, beta, aq) -> int:
    """ReLU(gamma * n * step + beta) rounded half to even onto the activation
    grid and saturated, in rational arithmetic."""
    from fractions import Fraction as F

    y = F(n) * F(step) * F(float(gamma)) + F(float(beta))
    code = round(max(y, F(0)) * 2 ** aq["frac_bits"])
    return min(code, 2 ** aq["total_bits"] - 1)


def _act_and_step():
    cfg = harness.resolve(harness.load_benchmark(),
                          "resnet9-w6a4-int.cams128").cfg
    aq = cfg["quant"]["act"]
    step = 2.0 ** -(aq["frac_bits"] + cfg["quant"]["weight"]["frac_bits"])
    return harness.model_module(cfg), aq, step


def test_reference_levels_start_where_exact_rounding_does():
    model, aq, step = _act_and_step()
    rng = np.random.default_rng(7)
    gamma = rng.uniform(0.5, 2.0, 48).astype(np.float32)
    beta = rng.uniform(-0.5, 0.5, 48).astype(np.float32)
    levels = model._levels(gamma, beta, step, aq)
    assert levels.shape == (48, 2 ** aq["total_bits"] - 1)
    for c in range(48):
        for q, n in enumerate(levels[c].astype(int), start=1):
            assert _exact_code(n, step, gamma[c], beta[c], aq) >= q
            assert _exact_code(n - 1, step, gamma[c], beta[c], aq) < q


# The float32 scale and shift (bit patterns) of channel 35 of c3 at seed
# 2200160032, of channel 471 of c3 at seed 2200160022 and of channel 58 of
# c0 at seed 936023273, a conv output (as a multiple of the conv's step,
# 2**-7) at which the affine lies within a float32 ulp of a half-step,
# 8.5000005, 7.4999996 and 0.50000002 codes, and the code it rounds to.  A
# float32 affine reads the last as 0.5 exactly, and rounds it to 0.
TIES = [(1061441829, 3180791088, 367, 9), (1071991298, 3189632696, 145, 7),
        (1068559507, 1028284928, 7, 1)]


@pytest.mark.parametrize("g_bits,b_bits,n,code", TIES,
                         ids=["up", "down", "first-level"])
def test_reference_rounds_a_tie_of_the_affine_exactly(g_bits, b_bits, n,
                                                      code):
    model, aq, step = _act_and_step()
    gamma = np.array([g_bits], np.uint32).view(np.float32)
    beta = np.array([b_bits], np.uint32).view(np.float32)
    levels = model._levels(gamma, beta, step, aq)[0]
    assert int((n >= levels).sum()) == code
    for m in (n - 1, n, n + 1):
        assert int((m >= levels).sum()) == _exact_code(m, step, gamma[0],
                                                       beta[0], aq)


def test_command_without_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "resnet9-w6a4-int.cams128", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout and '"correct"' not in p.stdout


# -- work and peaks ------------------------------------------------------------
def test_resnet9_macs_per_frame_at_paper_width():
    cfg = harness.resolve(harness.load_benchmark(),
                          "resnet9-w6a4-int.cams128").cfg
    assert (cfg["width"], cfg["img"], cfg["easy_augment"]) == (64, 32, True)
    model = harness.model_module(cfg)
    assert model.macs_per_frame(cfg) == 379_256_832
    assert work.frame_ops(model, cfg) == 4 * 379_256_832
    # the eight 3x3 convs as im2col matmuls: rows per frame, K, N
    assert model.weight_matmuls(cfg) == [
        ("c0", 1024, 27, 64), ("c1", 1024, 576, 128),
        ("r1a", 256, 1152, 128), ("r1b", 256, 1152, 128),
        ("c2", 256, 1152, 256), ("c3", 64, 2304, 512),
        ("r2a", 16, 4608, 512), ("r2b", 16, 4608, 512)]


def test_mvau_int_work_matches_a_hand_count():
    # r2a at batch 64: 64 frames x 4x4 pixels, K = 9 x 512, N = 512, 15 levels
    ops, nbytes = work.mvau_int_work(1024, 4608, 512, 15)
    assert ops == 2 * 1024 * 4608 * 512 == 4_831_838_208
    # int8 codes in, int8 weights, int32 thresholds, int32 codes out
    assert nbytes == 1024 * 4608 + 4608 * 512 + 4 * 512 * 15 + 4 * 1024 * 512
    t = work.mvau_int_bound_s(1024, 4608, 512, 15, 393e12, 819e9)
    assert t == max(ops / 393e12, nbytes / 819e9)


def test_unknown_device_kind_raises():
    assert peaks.peaks("TPU v5 lite")["int8_ops"] == 393e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")


# -- statistics and traffic ----------------------------------------------------
def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile([], 50) is None


def test_open_schedule_sends_the_same_gaps_in_a_seeded_order():
    a = traffic.open_schedule(500.0, 3.0, np.random.default_rng(1))
    b = traffic.open_schedule(500.0, 3.0, np.random.default_rng(2))
    assert len(a) == len(b) == 1500
    assert a[0] == b[0] == 0.0 and a[-1] < 3.0 and b[-1] < 3.0
    gaps = [np.sort(np.diff(np.append(s, 3.0))) for s in (a, b)]
    assert np.allclose(gaps[0], gaps[1])
    assert not np.allclose(a, b)


# -- the trace reduction, on a small trace recorded on the chip ----------------
def _recorded():
    with open(os.path.join(DATA, "window.json")) as f:
        meta = json.load(f)
    dt = devtrace.DeviceTrace.from_file(
        os.path.join(DATA, "window.xplane.pb"), meta["t_sync"])
    return meta, dt


def test_trace_reduction_on_a_recorded_chip_trace():
    meta, dt = _recorded()
    t0, t1 = meta["t0"], meta["t_end"]
    devs = dt.devices()
    assert devs and devs[0].startswith("/device:TPU:0")
    busy = dt.busy_s(t0, t1)
    # independent union: a millisecond-free sweep over sorted intervals
    ivs = sorted((max(s, t0), min(e, t1)) for _, s, e in dt.ops[devs[0]]
                 if e > t0 and s < t1)
    total, cur_s, cur_e = 0.0, None, None
    for s, e in ivs:
        if cur_e is None or s > cur_e:
            total += 0.0 if cur_e is None else cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    total += 0.0 if cur_e is None else cur_e - cur_s
    assert busy == pytest.approx(total, rel=1e-9)
    assert 0 < busy < t1 - t0
    gaps = dt.idle_gaps(t0, t1)
    assert sum(b - a for a, b in gaps) == pytest.approx(t1 - t0 - busy,
                                                        rel=1e-9)
    batches = [e for e in meta["spans"] if e["name"] == "serve.batch"
               and t0 <= e["t0"] and e["t0"] + e["dur_ms"] * 1e-3 <= t1]
    # eight conv MVAUs, two forward passes per backbone call
    calls = dt.per_span([(e["t0"], e["t0"] + e["dur_ms"] * 1e-3)
                         for e in batches], "mvau_int")
    assert batches and [n for n, _ in calls] == [16] * len(batches)
    named = devtrace.name_gaps(gaps, [(e["name"], e["t0"],
                                       e["t0"] + e["dur_ms"] * 1e-3)
                                      for e in meta["spans"]],
                               harness.GAP_PRIORITY)
    assert sum(named.values()) == pytest.approx(t1 - t0 - busy, rel=1e-9)


def test_trace_readers_on_the_recorded_trace():
    meta, dt = _recorded()
    cell = harness.resolve(harness.load_benchmark(), meta["cell"])
    run = harness.TraceRun(cell, meta["spans"], meta["t0"], meta["t_end"], dt,
                           meta["kind"], {"frames_per_s": 1000.0})
    roof = harness.reader("mvau_int_roofline")(run)
    assert 0 < roof <= 100
    idle = harness.reader("idle_share.cams128")(run)
    assert 0 < idle < 100
    rows = harness.reader("batch_rows_mean.cams128")(run)
    assert 1 <= rows <= 64
    b = run.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


# -- everything found by name ------------------------------------------------
def test_added_config_traffic_and_metric_files_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = harness.load_benchmark()
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "resnet9-w6a4-int.json")))
    cfg["engine"]["max_queue"] = 64
    (root / "bench" / "configs" / "resnet9-w6a4-int-q64.json").write_text(
        json.dumps(cfg))
    (root / "bench" / "traffic" / "burst.json").write_text(json.dumps(
        {"loop": "open", "rate": 2000.0,
         "pool_frames": 64, "why": "test"}))
    (root / "bench" / "layers" / "batches.burst.py").write_text(
        "def read(run):\n    return float(len(run.batches())) or None\n")
    bench["configs"].append({"name": "resnet9-w6a4-int-q64", "source": "x",
                             "file": "bench/configs/resnet9-w6a4-int-q64.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "resnet9-w6a4-int-q64.burst",
                               "config": "resnet9-w6a4-int-q64",
                               "traffic": "burst", "chips": 1, "why": "test"})
    fps = [m for m in bench["end_to_end"] if m["name"] == "frames_per_s"][0]
    fps["workloads"].append("resnet9-w6a4-int-q64.burst")
    bench["per_layer"].append({"name": "batches.burst", "unit": "batches",
                               "better": "higher", "source": "program_span",
                               "layer": "engine admission and coalescing",
                               "moves": "frames_per_s",
                               "workloads": ["resnet9-w6a4-int-q64.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.resolve(harness.load_benchmark(str(root)),
                           "resnet9-w6a4-int-q64.burst", str(root))
    assert cell.cfg["engine"]["max_queue"] == 64
    assert cell.traffic["rate"] == 2000.0
    assert [m["name"] for m in cell.per_layer] == ["batches.burst"]
    assert {m["name"] for m in cell.end_to_end} == {"frames_per_s",
                                                    "setup_s"}

    class Run:
        def batches(self):
            return [{}, {}]
    assert harness.reader("batches.burst", str(root))(Run()) == 2.0
    assert harness.model_module(cell.cfg, str(root)).plan(64)[0][0] == "c0"


def test_idle_gaps_are_named_by_what_the_worker_was_doing():
    spans = [("serve.queue", 0.0, 10.0), ("serve.batch", 1.0, 3.0),
             ("serve.exec", 2.0, 3.0), ("serve.respond", 3.0, 4.0),
             # one call's worker spans inside the above
             ("serve.fill", 0.5, 1.0), ("serve.exec.dispatch", 2.0, 2.1),
             ("serve.exec.wait", 2.1, 3.0), ("serve.head", 3.0, 3.2),
             ("serve.fulfil", 3.2, 3.4)]
    gaps = [(0.6, 0.8), (1.0, 1.5), (2.0, 2.06), (2.2, 2.4), (3.05, 3.1),
            (3.25, 3.3), (3.5, 3.7), (5.0, 6.0), (11.0, 12.0)]
    named = devtrace.name_gaps(gaps, spans, harness.GAP_PRIORITY)
    assert named == pytest.approx({
        "serve.fill": 0.2, "serve.batch": 0.5, "serve.exec.dispatch": 0.06,
        "serve.exec.wait": 0.2, "serve.head": 0.05, "serve.fulfil": 0.05,
        "serve.respond": 0.2, "serve.queue": 1.0, devtrace.NO_SPAN: 1.0})
    assert harness.GAP_PRIORITY[:5] == (
        "serve.exec.wait", "serve.exec.dispatch", "serve.head",
        "serve.fulfil", "serve.fill")


# -- a second backbone, added as new files only --------------------------------
TWIN = "resnet9twin"


def test_a_config_naming_no_recipe_fails_in_set_up():
    cell = tiny("resnet9-w6a4-int.cams128")
    cell.cfg["model"] = "no-such-backbone"
    with pytest.raises(ValueError) as err:
        harness.run_cell(cell, 2**33 + 23, 0.5, False, time.perf_counter())
    msg = str(err.value)
    assert "'resnet9-w6a4-int'" in msg and "'no-such-backbone'" in msg
    assert "'resnet9'" in msg                 # the recipes registered


def test_a_second_backbone_added_as_files_is_served_and_counted(tmp_path):
    """A recipe registered under a new name (resnet9's hooks and exporter),
    a reference file of that name whose ``macs_per_frame`` counts twice
    resnet9's, and a configuration and cell of it, each a new file or entry:
    the cell is served through that recipe, answers correctly, and its
    ``mfu.cams128`` comes from its own file's count."""
    from repro.core import recipes

    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    configs = root / "bench" / "configs"
    (configs / f"{TWIN}.py").write_text(
        (configs / "resnet9.py").read_text()
        + "\n\ndef macs_per_frame(cfg):\n"
        "    return 2 * sum(m * k * n for _, m, k, n in "
        "weight_matmuls(cfg))\n")
    cfg = json.loads((configs / "resnet9-w6a4-int.json").read_text())
    cfg["model"] = TWIN
    (configs / "twin-w6a4-int.json").write_text(json.dumps(cfg))
    bench = harness.load_benchmark()
    bench["configs"].append({"name": "twin-w6a4-int", "source": "x",
                             "file": "bench/configs/twin-w6a4-int.json",
                             "reduced": [], "why": "test"})
    name = "twin-w6a4-int.cams128"
    bench["workloads"].append({"name": name, "config": "twin-w6a4-int",
                               "traffic": "cams128", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("frames_per_s", "mfu.cams128"):
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    base = recipes.recipe("resnet9")
    recipes.register_recipe(
        TWIN, base.passes, exporter=base.exporter,
        init_params=base.init_params, feature_dim=base.feature_dim,
        forward=base.forward, quant_layers=base.quant_layers)
    served = []
    try:
        cell = tiny(name, root=str(root))
        out = harness.run_cell(
            cell, 2**33 + 29, 0.5, False, time.perf_counter(),
            fault=lambda s: served.append(s.recorder.deployed_model))
    finally:
        del recipes._RECIPES[TWIN]
    assert out.result["correct"] is True and out.result["failed"] == 0
    assert [dm.recipe_name for dm in served] == [TWIN]
    assert "mfu.cams128" in {m["name"] for m in cell.per_layer}

    fps = out.result["metrics"]["frames_per_s"]["value"]
    assert fps > 0
    base_cell = tiny("resnet9-w6a4-int.cams128")

    def mfu(c):
        run = harness.TraceRun(c, [], 0.0, 0.5, devtrace.DeviceTrace({}),
                               "TPU v5 lite", {"frames_per_s": fps})
        return harness.reader("mfu.cams128", c.root)(run)
    assert mfu(cell) == 2 * mfu(base_cell) > 0
