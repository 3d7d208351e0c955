"""The readers of the serve worker's per-call spans: ``fill_p50_ms``,
``dispatch_p50_ms``, ``copyback_lag_p50_ms``, ``head_p50_ms`` and
``fulfil_p50_ms`` (``.cams128``), on spans and a device trace built by hand,
on the window recorded before the program emitted these spans, and on a
short window of the program that does, recorded on the chip.

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests

Re-record the chip window with

  python3 bench/tests/record_trace.py --workload resnet9-w6a4-int.cams128 \\
      --seconds 0.3 --out bench/tests/data/spans
"""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import devtrace  # noqa: E402
import harness  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data")
CELL = "resnet9-w6a4-int.cams128"
METRICS = ("fill_p50_ms.cams128", "dispatch_p50_ms.cams128",
           "copyback_lag_p50_ms.cams128", "head_p50_ms.cams128",
           "fulfil_p50_ms.cams128")


def _span(name, t0, t1, trace, **attrs):
    return {"trace": trace, "span": f"{trace}-{name}", "parent": None,
            "name": name, "ts": 0.0, "t0": t0, "dur_ms": (t1 - t0) * 1e3,
            "status": "ok", "attrs": attrs}


def _call(k, fill, batch, split, head, fulfil_end):
    """One backbone call's spans on its own ``batch-`` trace (times in s)."""
    tr = f"batch-t-{k}"
    return [_span("serve.fill", *fill, tr, rows=64),
            _span("serve.batch", *batch, tr, n_real=64, bucket=64),
            _span("serve.exec.dispatch", split[0], split[1], tr),
            _span("serve.exec.wait", split[1], batch[1], tr),
            _span("serve.head", head[0], head[1], tr, rows=64),
            _span("serve.fulfil", head[1], fulfil_end, tr, requests=64)]


def _synthetic():
    """Three calls.  Fill 4, 2, 3 ms; dispatch 1.0, 0.5, 2.0 ms; head 1.0,
    0.5, 0.25 ms; fulfil 2.0, 2.5, 1.0 ms; copy-back lag 2, 1, 4 ms."""
    spans = (_call(1, (1.000, 1.004), (1.005, 1.030), (1.006, 1.007),
                   (1.031, 1.032), 1.034)
             + _call(2, (1.034, 1.036), (1.036, 1.060), (1.037, 1.0375),
                     (1.061, 1.0615), 1.064)
             + _call(3, (1.064, 1.067), (1.067, 1.090), (1.068, 1.070),
                     (1.091, 1.09125), 1.09225))
    # a call cut by the window's start: its wait is in, its dispatch not
    spans += [_span("serve.exec.dispatch", 0.990, 1.000, "batch-t-0"),
              _span("serve.exec.wait", 1.000, 1.0004, "batch-t-0")]
    ops = [("while", 1.0065, 1.028),       # the latest end, not the last start
           ("fusion", 1.0070, 1.010), ("copy", 1.0265, 1.027),
           ("cosine", 1.0312, 1.0315),     # the head: after the call
           ("fusion", 1.038, 1.059),
           ("fusion", 1.069, 1.086)]
    dt = devtrace.DeviceTrace({"/device:TPU:0": sorted(
        ops, key=lambda o: o[1])})
    return spans, dt


def _run(spans, dt, t0=1.0, t1=1.1):
    cell = harness.resolve(harness.load_benchmark(), CELL)
    return harness.TraceRun(cell, spans, t0, t1, dt, "TPU v5 lite",
                            {"frames_per_s": 1000.0})


def _read(name, run):
    return harness.reader(name)(run)


@pytest.mark.parametrize("name,want", [
    ("fill_p50_ms.cams128", 3.0), ("dispatch_p50_ms.cams128", 1.0),
    ("copyback_lag_p50_ms.cams128", 2.0), ("head_p50_ms.cams128", 0.5),
    ("fulfil_p50_ms.cams128", 2.0)])
def test_reader_on_hand_built_spans(name, want):
    spans, dt = _synthetic()
    assert _read(name, _run(spans, dt)) == pytest.approx(want, abs=1e-9)


def test_copyback_lag_skips_a_call_without_device_ops():
    spans, dt = _synthetic()
    # drop call 3's device work: its lag goes, the median of (2, 1) is 1
    dt.ops["/device:TPU:0"] = [o for o in dt.ops["/device:TPU:0"]
                               if not 1.067 <= o[1] <= 1.090]
    assert _read("copyback_lag_p50_ms.cams128",
                 _run(spans, dt)) == pytest.approx(1.0)


def _recorded(sub):
    root = os.path.join(DATA, sub) if sub else DATA
    with open(os.path.join(root, "window.json")) as f:
        meta = json.load(f)
    dt = devtrace.DeviceTrace.from_file(
        os.path.join(root, "window.xplane.pb"), meta["t_sync"])
    return meta, dt, _run(meta["spans"], dt, meta["t0"], meta["t_end"])


@pytest.mark.parametrize("name", METRICS)
def test_reader_returns_none_without_its_spans(name):
    """On spans of the engine before it emitted per-call ones: the hand-built
    calls without them, and the window recorded on the chip before (as the
    parent program reads)."""
    spans, dt = _synthetic()
    old = [e for e in spans if e["name"] == "serve.batch"]
    assert _read(name, _run(old, dt)) is None
    _, _, run = _recorded("")
    assert run.batches() and _read(name, run) is None


def test_readers_on_a_recorded_chip_window():
    meta, dt, run = _recorded("spans")
    vals = {n: _read(n, run) for n in METRICS}
    assert all(v is not None for v in vals.values()), vals
    exec_ms = harness.reader("exec_p50_ms.cams128")(run)
    assert 0 < vals["dispatch_p50_ms.cams128"] < exec_ms
    assert 0 <= vals["copyback_lag_p50_ms.cams128"] < exec_ms
    for n in ("fill_p50_ms.cams128", "head_p50_ms.cams128",
              "fulfil_p50_ms.cams128"):
        assert 0 < vals[n] < exec_ms
    # per backbone call one of each: every request classifies, so each
    # call has one classify run
    for b in run.batches():
        names = sorted(e["name"] for e in meta["spans"]
                       if e["trace"] == b["trace"] and e is not b)
        assert names == ["serve.exec.dispatch", "serve.exec.wait",
                         "serve.fill", "serve.fulfil", "serve.head"], names


def test_copyback_lag_matches_an_independent_count_on_the_chip_window():
    meta, dt, run = _recorded("spans")
    disp = {e["trace"]: e["t0"] for e in run.spans("serve.exec.dispatch")}
    lags = []
    for w in run.spans("serve.exec.wait"):
        a, b = disp[w["trace"]], w["t0"] + w["dur_ms"] * 1e-3
        ends = [e for evs in dt.ops.values() for _, s, e in evs if a <= s <= b]
        assert ends                           # the backbone ran in the call
        lags.append((b - max(ends)) * 1e3)
    lags.sort()
    assert _read("copyback_lag_p50_ms.cams128", run) == pytest.approx(
        lags[(len(lags) + 1) // 2 - 1])


def test_worker_spans_cover_the_recorded_chip_window():
    """Fill, batch, head and fulfil leave no more than 5% of the window in
    which the worker's time is not accounted for."""
    meta, _, _ = _recorded("spans")
    t0, t1 = meta["t0"], meta["t_end"]
    ivs = sorted((max(e["t0"], t0), min(e["t0"] + e["dur_ms"] * 1e-3, t1))
                 for e in meta["spans"]
                 if e["name"] in ("serve.fill", "serve.batch", "serve.head",
                                  "serve.fulfil"))
    covered, reach = 0.0, t0
    for s, e in ivs:
        if e > reach:
            covered += e - max(s, reach)
            reach = e
    assert covered / (t1 - t0) >= 0.95



# What every per-layer reader of both cells returned on the two recorded
# windows, at 1,000 frames/s, before the model's work counts moved from
# ``work.py`` into its reference file: how a reader finds its counts may
# change, what it reads may not.
RECORDED = {
    "": {"batch_rows_mean.cams128": 64.0,
         "exec_p50_ms.cams128": 28.286667999992687,
         "idle_share.cams128": 58.24032766671665,
         "fill_p50_ms.cams128": None, "dispatch_p50_ms.cams128": None,
         "copyback_lag_p50_ms.cams128": None, "head_p50_ms.cams128": None,
         "fulfil_p50_ms.cams128": None},
    "spans": {"batch_rows_mean.cams128": 64.0,
              "exec_p50_ms.cams128": 34.96390800000171,
              "idle_share.cams128": 38.37952833326159,
              "fill_p50_ms.cams128": 0.13586000000032072,
              "dispatch_p50_ms.cams128": 0.40305000000273594,
              "copyback_lag_p50_ms.cams128": 2.4447180000066737,
              "head_p50_ms.cams128": 1.8203790000015374,
              "fulfil_p50_ms.cams128": 1.2638500000008435},
}
RECORDED_BY_CELL = {
    ("", "resnet9-w6a4-int.cams128"): {
        "mvau_int_roofline": 11.310763090104729,
        "mfu.cams128": 0.3860120427480916},
    ("spans", "resnet9-w6a4-int.cams128"): {
        "mvau_int_roofline": 11.310784355987973,
        "mfu.cams128": 0.3860120427480916},
    ("", "resnet9-w6a4-f32.cams128"): {"mfu.cams128": 0.7700646335025381},
    ("spans", "resnet9-w6a4-f32.cams128"): {"mfu.cams128": 0.7700646335025381},
}


@pytest.mark.parametrize("sub,cell_name", sorted(RECORDED_BY_CELL),
                         ids=lambda v: v or "window")
def test_readers_read_as_recorded_on_both_windows(sub, cell_name):
    meta, dt, _ = _recorded(sub)
    cell = harness.resolve(harness.load_benchmark(), cell_name)
    run = harness.TraceRun(cell, meta["spans"], meta["t0"], meta["t_end"], dt,
                           meta["kind"], {"frames_per_s": 1000.0})
    want = dict(RECORDED[sub], **RECORDED_BY_CELL[sub, cell_name])
    assert {m["name"] for m in cell.per_layer} == set(want)
    assert {n: _read(n, run) for n in want} == want
