"""repro.obs — the unified observability spine (ISSUE 8).

Covers: span nesting/parent IDs and the JSONL event schema, ring-buffer
eviction order, Prometheus text exposition (label escaping, cumulative
histogram buckets), the disabled fast path (singleton null span, no
exporter traffic), the ServeMetrics consistent-snapshot contract under a
concurrent hammer (the satellite-a race regression), request-lifecycle
tracing through ServeEngine and trace propagation through ServeCluster,
PassManager compile spans, DeployedModel.profile() cost attribution and its
sweep-record plumbing, the summarize renderer, and the repro.launch shims
left behind by the hlo_analysis/diagnose fold.
"""

import json
import threading

import numpy as np
import pytest

import repro.obs as obs
from repro.obs import (
    EVENT_FIELDS,
    NULL_SPAN,
    JsonlExporter,
    MetricsRegistry,
    RingBufferExporter,
    Tracer,
    escape_label_value,
    read_jsonl,
)
from repro.obs.summarize import render, render_tree, stage_stats
from repro.serve import ArtifactRegistry, ServeEngine
from repro.serve.metrics import ServeMetrics

IMG = 8


def _toy_feats(x):
    """A fake backbone: (n, H, W, C) -> (n, 8) with no compilation."""
    x = np.asarray(x, np.float32)
    return x.reshape(x.shape[0], -1)[:, :8]


def _traced_pair():
    ring = RingBufferExporter()
    return Tracer(exporter=ring, enabled=True), ring


# ---------------------------------------------------------------------------
# tracer core: spans, nesting, schema
# ---------------------------------------------------------------------------
def test_span_nesting_and_parent_ids():
    tr, ring = _traced_pair()
    with tr.span("root", attrs={"k": 1}) as root:
        child_id = tr.record("child", 1.0, 2.0, trace=root.trace,
                             parent=root.span_id)
        with tr.span("grand", trace=root.trace, parent=child_id) as g:
            g.set("deep", True)
    ev = ring.events()
    assert [e["name"] for e in ev] == ["child", "grand", "root"]
    child, grand, root_ev = ev
    assert child["trace"] == grand["trace"] == root_ev["trace"]
    assert child["parent"] == root_ev["span"]
    assert grand["parent"] == child["span"]
    assert root_ev["parent"] is None
    assert root_ev["attrs"] == {"k": 1}
    assert grand["attrs"] == {"deep": True}
    assert child["dur_ms"] == pytest.approx(1000.0)


def test_event_schema_and_span_error_status():
    tr, ring = _traced_pair()
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("x")
    (ev,) = ring.events()
    assert tuple(sorted(ev)) == tuple(sorted(EVENT_FIELDS))
    assert ev["status"] == "error:ValueError"


def test_record_returns_span_id_for_chaining():
    tr, ring = _traced_pair()
    t = tr.new_trace()
    sid = tr.record("a", 0.0, 0.5, trace=t)
    tr.record("b", 0.5, 0.6, trace=t, parent=sid)
    a, b = ring.events()
    assert sid and a["span"] == sid and b["parent"] == sid


def test_disabled_fast_path_allocates_only_the_id():
    ring = RingBufferExporter()
    tr = Tracer(exporter=ring, enabled=False)
    # the null span is a module singleton — no per-call span objects
    assert tr.span("a") is NULL_SPAN
    assert tr.span("b", attrs={"x": 1}) is NULL_SPAN
    NULL_SPAN.set("k", 1).end()            # all no-ops
    assert tr.record("c", 0.0, 1.0, trace="t") == ""
    # the trace ID is the one allowed allocation, and stays unique
    ids = {tr.new_trace() for _ in range(16)}
    assert len(ids) == 16
    assert len(ring) == 0
    # enabling without an exporter stays disabled (nowhere to export)
    assert not Tracer(exporter=None, enabled=True).enabled


def test_configure_flips_global_default_tracer():
    tr = obs.get_tracer()
    assert tr is obs.get_tracer()
    ring = RingBufferExporter()
    try:
        assert obs.configure(ring) is tr and tr.enabled
        tr.record("x", 0.0, 1.0, trace=tr.new_trace())
        assert len(ring) == 1
    finally:
        obs.configure(enabled=False)
    assert not tr.enabled


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------
def test_ring_buffer_evicts_oldest_in_order():
    ring = RingBufferExporter(capacity=4)
    tr = Tracer(exporter=ring, enabled=True)
    for i in range(7):
        tr.record(f"s{i}", 0.0, 1.0, trace="t")
    assert [e["name"] for e in ring.events()] == ["s3", "s4", "s5", "s6"]
    assert [e["name"] for e in ring.drain()] == ["s3", "s4", "s5", "s6"]
    assert len(ring) == 0 and ring.events() == []


def test_jsonl_round_trip_preserves_schema(tmp_path):
    path = tmp_path / "trace.jsonl"
    with JsonlExporter(str(path)) as exp:
        tr = Tracer(exporter=exp, enabled=True)
        t = tr.new_trace()
        root = tr.record("outer", 0.0, 2.0, trace=t,
                         attrs={"tenant": "acme", "n": 3})
        tr.record("inner", 0.5, 1.0, trace=t, parent=root, status="ok")
    back = read_jsonl(str(path))
    assert [e["name"] for e in back] == ["outer", "inner"]
    for e in back:
        assert tuple(sorted(e)) == tuple(sorted(EVENT_FIELDS))
    assert back[0]["attrs"] == {"tenant": "acme", "n": 3}
    assert back[1]["parent"] == back[0]["span"]
    # every line is independently valid JSON (streaming consumers)
    for line in path.read_text().splitlines():
        json.loads(line)


# ---------------------------------------------------------------------------
# metrics registry / Prometheus exposition
# ---------------------------------------------------------------------------
def test_prometheus_label_escaping():
    assert escape_label_value('bad"x\nline\\') == 'bad\\"x\\nline\\\\'
    reg = MetricsRegistry()
    c = reg.counter("t_total", "help", labelnames=("path",))
    c.inc(path='a"b\nc\\d')
    text = reg.render()
    assert 't_total{path="a\\"b\\nc\\\\d"} 1' in text
    assert "# HELP t_total help" in text
    assert "# TYPE t_total counter" in text


def test_histogram_cumulative_buckets_and_sum():
    reg = MetricsRegistry()
    h = reg.histogram("lat_ms", "latency", buckets=(1, 10, 100))
    for v in (0.5, 5.0, 50.0, 500.0):
        h.observe(v)
    text = reg.render()
    assert 'lat_ms_bucket{le="1"} 1' in text
    assert 'lat_ms_bucket{le="10"} 2' in text
    assert 'lat_ms_bucket{le="100"} 3' in text
    assert 'lat_ms_bucket{le="+Inf"} 4' in text
    assert "lat_ms_count 4" in text
    assert "lat_ms_sum 555.5" in text


def test_registry_rejects_conflicting_reregistration():
    reg = MetricsRegistry()
    reg.counter("x_total", "h")
    assert reg.counter("x_total", "h") is reg.counter("x_total", "h")
    with pytest.raises(ValueError):
        reg.gauge("x_total", "h")
    with pytest.raises(ValueError):
        reg.counter("x_total", "h", labelnames=("a",))


# ---------------------------------------------------------------------------
# ServeMetrics: the consistent-snapshot contract (satellite-a regression)
# ---------------------------------------------------------------------------
def test_serve_metrics_snapshot_consistent_under_hammer():
    """Writers hammer every recording path while readers take snapshots.
    All batches are (n_real=4, bucket=8), so padded_frac is EXACTLY 0.5 in
    every snapshot that sees >= 1 batch, and mean_batch exactly 4.0 — the
    pre-registry implementation could tear between the counter reads and
    show neither.  Final totals must be exact."""
    m = ServeMetrics()
    n_threads, n_iter = 6, 300
    stop = threading.Event()
    bad = []

    def writer():
        for _ in range(n_iter):
            m.record_request(0.01, tenant="t")
            m.record_batch(4, 8)
            m.record_rejected(tenant="t", over_quota=True)
            m.record_request(0.0, ok=False, tenant="t")
            m.observe_queue_depth(3)

    def reader():
        while not stop.is_set():
            s = m.snapshot()
            if s["batches"] and not (s["padded_frac"] == 0.5
                                     and s["mean_batch"] == 4.0):
                bad.append(s)
            m.prometheus()
            m.tenant_snapshot()

    readers = [threading.Thread(target=reader) for _ in range(2)]
    writers = [threading.Thread(target=writer) for _ in range(n_threads)]
    for t in readers + writers:
        t.start()
    for t in writers:
        t.join()
    stop.set()
    for t in readers:
        t.join()
    assert not bad, f"torn snapshot(s): {bad[:2]}"
    total = n_threads * n_iter
    s = m.snapshot()
    assert s["completed"] == total and s["failed"] == total
    assert s["rejected"] == total and s["over_quota"] == total
    assert s["batches"] == total and s["max_queue_depth"] == 3
    ts = m.tenant_snapshot()["t"]
    assert ts["completed"] == total and ts["over_quota"] == total
    text = m.prometheus()
    assert f"repro_serve_completed_total {total}" in text
    assert ('repro_serve_tenant_requests_total'
            '{tenant="t", status="completed"}') in text


# ---------------------------------------------------------------------------
# request-lifecycle tracing through the engine / cluster
# ---------------------------------------------------------------------------
def test_engine_request_trace_covers_lifecycle():
    tr, ring = _traced_pair()
    reg = ArtifactRegistry()
    reg.register("toy", _toy_feats, default=True)
    rng = np.random.default_rng(0)
    with ServeEngine(reg, max_batch=8, batch_wait_ms=1.0, tracer=tr) as eng:
        eng.submit_register(
            "c0", rng.random((2, IMG, IMG, 3), np.float32)).result(timeout=30)
        fut = eng.submit_classify(
            rng.random((1, IMG, IMG, 3), np.float32), tenant="acme")
        fut.result(timeout=30)
        trace = fut.trace_id
    ev = [e for e in ring.events() if e["trace"] == trace]
    names = {e["name"] for e in ev}
    assert names == {"serve.request", "serve.admission", "serve.queue",
                     "serve.coalesce", "serve.exec", "serve.respond"}
    root = ServeEngine._root_span(trace)
    (root_ev,) = [e for e in ev if e["name"] == "serve.request"]
    assert root_ev["span"] == root and root_ev["status"] == "ok"
    assert root_ev["attrs"]["tenant"] == "acme"
    assert root_ev["attrs"]["kind"] == "classify"
    for e in ev:
        if e is not root_ev:
            assert e["parent"] == root
    # span windows tile the request: admission ends where queue starts, etc.
    by = {e["name"]: e for e in ev}
    for a, b in (("serve.admission", "serve.queue"),
                 ("serve.queue", "serve.coalesce")):
        assert by[b]["t0"] >= by[a]["t0"]
    # the batch-scope span rides its own trace with padding accounting
    batch = [e for e in ring.events() if e["name"] == "serve.batch"]
    assert batch and batch[0]["trace"].startswith("batch-")
    a = batch[-1]["attrs"]
    assert a["n_real"] + a["padded"] == a["bucket"]


def test_engine_rejection_still_emits_root_span():
    tr, ring = _traced_pair()
    reg = ArtifactRegistry()
    reg.register("toy", _toy_feats, default=True)
    eng = ServeEngine(reg, max_batch=4, tracer=tr, start=False)
    eng.stop()
    from repro.serve import ServeOverload
    with pytest.raises(ServeOverload):
        eng.submit_classify(np.zeros((1, IMG, IMG, 3), np.float32))
    roots = [e for e in ring.events() if e["name"] == "serve.request"]
    assert roots and roots[-1]["status"] == "rejected:stopped"


# ---------------------------------------------------------------------------
# per-call spans of the worker's batch cycle, and the head's trace counter
# ---------------------------------------------------------------------------
def _end(e):
    return e["t0"] + e["dur_ms"] * 1e-3


def _serve_one_batch(tracer):
    """Eight rows queued before the worker starts, so they coalesce into ONE
    backbone call: register c0, two classifies, register c1, a classify of
    three frames -- two classify runs split by the register.  Returns the
    answers in submit order."""
    reg = ArtifactRegistry()
    reg.register("toy", _toy_feats, default=True)
    rng = np.random.default_rng(3)

    def frames(n):
        return rng.random((n, IMG, IMG, 3), np.float32)

    eng = ServeEngine(reg, max_batch=8, batch_wait_ms=1.0, tracer=tracer,
                      start=False)
    futs = [eng.submit_register("c0", frames(2)),
            eng.submit_classify(frames(1)), eng.submit_classify(frames(1)),
            eng.submit_register("c1", frames(1)),
            eng.submit_classify(frames(3))]
    eng.start()
    try:
        return [f.result(timeout=30) for f in futs]
    finally:
        eng.stop()


def _call_spans():
    """The traced batch's ``serve.batch`` span and its children by name."""
    tr, ring = _traced_pair()
    _serve_one_batch(tr)
    ev = ring.events()
    (batch,) = [e for e in ev if e["name"] == "serve.batch"]
    kids = {}
    for e in ev:
        if e["trace"] == batch["trace"] and e is not batch:
            kids.setdefault(e["name"], []).append(e)
    execs = {(e["t0"], e["dur_ms"]) for e in ev if e["name"] == "serve.exec"}
    return batch, kids, execs


def test_each_backbone_call_emits_one_span_set_on_its_batch_trace():
    batch, kids, _ = _call_spans()
    assert {n: len(v) for n, v in kids.items()} == {
        "serve.fill": 1, "serve.exec.dispatch": 1, "serve.exec.wait": 1,
        "serve.head": 2, "serve.fulfil": 2}          # one per classify run
    assert all(e["parent"] == batch["span"]
               for v in kids.values() for e in v)
    assert batch["attrs"]["n_real"] == 8
    assert kids["serve.fill"][0]["attrs"] == {"rows": 8}
    assert [e["attrs"]["rows"] for e in kids["serve.head"]] == [2, 3]
    assert [e["attrs"]["requests"] for e in kids["serve.fulfil"]] == [2, 1]
    assert all(e["status"] == "ok" for e in kids["serve.head"])


def test_dispatch_and_wait_tile_the_exec_span():
    batch, kids, execs = _call_spans()
    ((x0, x_ms),) = execs               # every request shares the call's
    (d,), (w,) = kids["serve.exec.dispatch"], kids["serve.exec.wait"]
    assert d["t0"] == x0
    assert w["t0"] == pytest.approx(_end(d), abs=1e-9)
    assert _end(w) == pytest.approx(x0 + x_ms * 1e-3, abs=1e-9)
    assert d["dur_ms"] + w["dur_ms"] == pytest.approx(x_ms, abs=1e-6)
    # serve.batch keeps its interval: it ends where the exec span ends
    assert _end(batch) == pytest.approx(_end(w), abs=1e-9)


def test_fill_precedes_the_batch_and_head_follows_the_exec():
    batch, kids, _ = _call_spans()
    (fill,), (w,) = kids["serve.fill"], kids["serve.exec.wait"]
    assert fill["t0"] < _end(fill) <= batch["t0"]
    heads, fulfils = kids["serve.head"], kids["serve.fulfil"]
    for h, f in zip(heads, fulfils):
        assert h["t0"] >= _end(w) - 1e-9
        assert f["t0"] == pytest.approx(_end(h), abs=1e-9)
    assert heads[1]["t0"] >= _end(fulfils[0]) - 1e-9


def test_disabled_tracer_emits_nothing_and_answers_the_same():
    ring = RingBufferExporter()
    off = _serve_one_batch(Tracer(exporter=ring, enabled=False))
    on = _serve_one_batch(_traced_pair()[0])
    assert ring.events() == []
    assert off[0] == on[0] == 2 and off[3] == on[3] == 1
    for a, b in zip(off[1:3] + off[4:], on[1:3] + on[4:]):
        assert a.class_ids == b.class_ids
        np.testing.assert_array_equal(a.sims, b.sims)


def _toy13_feats(x):
    """A fake backbone with 13 features: a width no other head in the
    suite sees, so the process-wide head counter meets fresh shapes."""
    x = np.asarray(x, np.float32)
    return x.reshape(x.shape[0], -1)[:, :13]


def test_head_trace_counter_flat_after_warmup_one_per_class_count():
    from repro.fsl import ncm

    reg = ArtifactRegistry()
    reg.register("toy13", _toy13_feats, default=True)
    rng = np.random.default_rng(5)

    def frames(n):
        return rng.random((n, IMG, IMG, 3), np.float32)

    head = ServeEngine.HEAD_TRACES
    with ServeEngine(reg, max_batch=8, batch_wait_ms=1.0) as eng:
        for c in ("c0", "c1"):
            eng.submit_register(c, frames(2)).result(timeout=30)
        before = ncm.trace_count()
        base = eng.warmup(img=IMG)       # the head at 2 classes, buckets 1-8
        assert base == {"toy13": None, head: ncm.trace_count()}
        assert base[head] - before == 4
        for n in range(1, 9):            # every bucket, exact and padded
            eng.submit_classify(frames(n)).result(timeout=30)
        assert eng.trace_counts() == base
        eng.submit_register("c2", frames(1)).result(timeout=30)
        res = eng.submit_classify(frames(1)).result(timeout=30)
        assert res.sims.shape == (1, 3)
        assert eng.trace_counts() == {**base, head: base[head] + 1}


def test_cluster_propagates_one_trace_id():
    from repro.serve.cluster import ServeCluster, TenantRegistry

    tr, ring = _traced_pair()
    registry = TenantRegistry()
    registry.register_backbone("toy", _toy_feats, default=True)
    rng = np.random.default_rng(1)
    with ServeCluster(registry, replicas=2, max_batch=8, batch_wait_ms=1.0,
                      tracer=tr) as cluster:
        cluster.add_tenant("acme")
        cluster.submit_register(
            "acme", "c0",
            rng.random((2, IMG, IMG, 3), np.float32)).result(timeout=30)
        fut = cluster.submit_classify(
            "acme", rng.random((1, IMG, IMG, 3), np.float32))
        fut.result(timeout=30)
        trace = fut.trace_id
    ev = [e for e in ring.events() if e["trace"] == trace]
    names = {e["name"] for e in ev}
    # ONE trace ID covers routing AND the full engine lifecycle
    assert {"cluster.route", "serve.request", "serve.queue",
            "serve.exec"} <= names
    (route,) = [e for e in ev if e["name"] == "cluster.route"]
    assert route["parent"] == ServeEngine._root_span(trace)
    assert route["attrs"]["tenant"] == "acme"
    assert route["attrs"]["failovers"] == 0
    assert route["attrs"]["replica"] == cluster.home_replica("acme")


# ---------------------------------------------------------------------------
# compiler telemetry + cost attribution (real compile, shared fixture)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def traced_compile():
    import jax

    import repro
    from repro.core.quant import QuantConfig
    from repro.models import resnet9

    tr, ring = _traced_pair()
    params = resnet9.init_params(jax.random.PRNGKey(0), 4)
    dm = repro.compile(params, QuantConfig.grid_point(6, 4),
                       recipe="resnet9", datapath="int", tracer=tr)
    return dm, ring.events()


def test_pass_manager_emits_compile_spans(traced_compile):
    _, events = traced_compile
    roots = [e for e in events if e["name"] == "compile.build"]
    assert len(roots) == 1
    root = roots[0]
    passes = [e for e in events if e["name"] == "compile.pass"]
    assert len(passes) == root["attrs"]["n_passes"] >= 3
    assert all(e["trace"] == root["trace"] for e in passes)
    assert all(e["parent"] == root["span"] for e in passes)
    for e in passes:
        a = e["attrs"]
        assert {"pass", "nodes_before", "nodes_after"} <= set(a)
    # the fusion pass must be in there and must have shrunk the graph
    # (op_delta is a per-op count-change dict, negative = nodes removed)
    fuse = [e for e in passes if "fuse" in e["attrs"]["pass"]]
    assert fuse and any(v < 0 for e in fuse
                        for v in e["attrs"]["op_delta"].values())
    assert root["attrs"]["total_ms"] > 0


def test_deployed_model_profile_cost_table(traced_compile):
    dm, _ = traced_compile
    x = np.zeros((2, 16, 16, 3), np.float32)
    prof = dm.profile(x, xla=False)
    assert prof["batch"] == 2 and prof["xla"] is None
    nodes = prof["nodes"]
    assert nodes, "profile returned an empty node table"
    for row in nodes:
        assert {"tensor", "op", "kernel", "flops", "bytes",
                "est_ms", "bound", "share"} <= set(row)
    tot = prof["totals"]
    assert tot["flops"] == sum(r["flops"] for r in nodes) > 0
    assert tot["bytes"] == sum(r["bytes"] for r in nodes) > 0
    assert sum(r["share"] for r in nodes) == pytest.approx(1.0)
    # matmul-family nodes dominate a convnet's FLOPs
    mv = [r for r in nodes if r["op"] in ("mvau_int", "mvau", "matmul",
                                          "matmul_int")]
    assert sum(r["flops"] for r in mv) > 0.5 * tot["flops"]
    from repro.obs.costmodel import render_profile
    text = render_profile(prof)
    assert text.startswith("profile: batch=2")
    assert "modeled" in text and nodes[0]["op"] in text


def test_profile_peaks_keyed_by_device_kind(traced_compile):
    """The roofline peaks come from the row of the device kind profiled;
    a kind with no row raises instead of borrowing another's peaks."""
    from repro.obs.costmodel import device_peaks

    dm, _ = traced_compile
    x = np.zeros((1, 16, 16, 3), np.float32)
    cpu = dm.profile(x, xla=False)
    v5e = dm.profile(x, xla=False, device_kind="TPU v5 lite")
    assert cpu["device_kind"] == "cpu" and v5e["device_kind"] == "TPU v5 lite"
    assert v5e["totals"]["est_ms"] < cpu["totals"]["est_ms"]
    assert device_peaks("TPU v5 lite")["int8_ops"] == 393e12
    with pytest.raises(KeyError, match="no peak rates"):
        dm.profile(x, xla=False, device_kind="TPU v99")


@pytest.mark.slow
def test_run_point_records_modeled_cost():
    from repro.explore.sweep import run_point

    kw = dict(width=4, steps=2, episodes=2, batch=8, bench_batch=2,
              bench_iters=1, n_base=6, n_novel=5, seed=3)
    rec = run_point(4, 4, **kw).record
    assert rec["modeled_ms"] > 0
    assert rec["modeled_flops"] > 0 and rec["modeled_bytes"] > 0
    top = rec["cost_top"]
    assert top and {"tensor", "op", "kernel", "share"} <= set(top)
    assert 0 < top["share"] <= 1


# ---------------------------------------------------------------------------
# summarize renderer
# ---------------------------------------------------------------------------
def _fake_events():
    def mk(**kw):
        return {**dict.fromkeys(EVENT_FIELDS), "attrs": {}, "status": "ok",
                **kw}
    return [
        mk(trace="req-1", span="req-1-00", parent=None, name="serve.request",
           t0=0.0, dur_ms=10.0, attrs={"tenant": "acme"}),
        mk(trace="req-1", span="s1", parent="req-1-00", name="serve.queue",
           t0=1.0, dur_ms=6.0),
        mk(trace="req-1", span="s2", parent="req-1-00", name="serve.exec",
           t0=7.0, dur_ms=3.0),
        mk(trace="batch-1", span="s3", parent=None, name="serve.batch",
           t0=7.0, dur_ms=3.0,
           attrs={"n_real": 3, "padded": 1, "bucket": 4, "requests": 3}),
    ]


def test_stage_stats_and_render():
    ev = _fake_events()
    stats = stage_stats(ev)
    assert stats["serve.queue"]["count"] == 1
    assert stats["serve.queue"]["p50_ms"] == pytest.approx(6.0)
    assert sum(s["share"] for s in stats.values()) == pytest.approx(1.0)
    out = render(ev, trees=1)
    assert "serve.queue" in out and "serve.exec" in out
    assert "1 batches, 3 real + 1 padded rows" in out
    assert "25.0% waste" in out
    assert "trace req-1" in out          # the slowest-tree view
    assert render([]) == "no events"


def test_render_tree_nests_children():
    out = render_tree(_fake_events(), "req-1")
    lines = out.splitlines()
    assert "trace req-1 (3 spans)" in lines[0]
    req = next(i for i, l in enumerate(lines) if "serve.request" in l)
    qu = next(i for i, l in enumerate(lines) if "serve.queue" in l)
    assert qu > req
    # children indent one level deeper than the root
    assert (len(lines[qu]) - len(lines[qu].lstrip())
            > len(lines[req]) - len(lines[req].lstrip()))
    assert "tenant=acme" in lines[req]
    assert "no spans" in render_tree([], "missing")


# ---------------------------------------------------------------------------
# launch-package fold: the shims must keep the old import paths alive
# ---------------------------------------------------------------------------
def test_launch_hlo_analysis_shim_reexports():
    from repro.launch import hlo_analysis as shim
    from repro.obs import hlo

    for name in ("analyze", "parse_module", "top_collectives", "top_dots",
                 "trip_count", "Computation"):
        assert getattr(shim, name) is getattr(hlo, name)


def test_launch_diagnose_shim_reexports():
    from repro.launch import diagnose as shim
    from repro.obs import diagnose as real

    assert shim.main is real.main
    assert shim.lower_and_text is real.lower_and_text
