"""repro.serve — the real-time few-shot serving runtime (ISSUE 3).

Covers: bucket math, the online PrototypeStore's bit-for-bit contract with
offline NCM (single-shot, imbalanced, chunked/interleaved arrival), the
artifact registry's hot-swap, the DeployedModel bucket cache, and the
ServeEngine end to end — mixed register/classify traffic, strict-FIFO
semantics, backpressure, metrics, and (slow) a 1000-request soak with a
zero-retrace assertion.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core.quant import QuantConfig, fake_quant
from repro.fsl import ncm
from repro.fsl.pipeline import FSLPipeline
from repro.models import resnet9
from repro.serve import (
    ArtifactRegistry,
    PrototypeStore,
    ServeEngine,
    ServeOverload,
    bucket_for,
    pad_to_bucket,
    pow2_buckets,
)

WIDTH, IMG = 4, 16
QCFG = QuantConfig.paper_w6a4()


@pytest.fixture(scope="module")
def served():
    """One compiled int artifact + pipeline shared by the engine tests."""
    params = resnet9.init_params(jax.random.PRNGKey(0), WIDTH)
    pipe = FSLPipeline(width=WIDTH, qcfg=QCFG)
    return pipe, params


def _frames(rng, n):
    return rng.random((n, IMG, IMG, 3)).astype(np.float32)


# ---------------------------------------------------------------------------
# bucketing
# ---------------------------------------------------------------------------
def test_pow2_buckets_cover_max_batch():
    assert pow2_buckets(64) == (1, 2, 4, 8, 16, 32, 64)
    assert pow2_buckets(48) == (1, 2, 4, 8, 16, 32, 48)
    assert pow2_buckets(1) == (1,)


def test_bucket_for_rounds_up():
    bs = pow2_buckets(16)
    assert [bucket_for(n, bs) for n in (1, 2, 3, 5, 8, 9, 16)] == \
        [1, 2, 4, 8, 8, 16, 16]
    with pytest.raises(ValueError):
        bucket_for(17, bs)
    with pytest.raises(ValueError):
        bucket_for(0, bs)


def test_pad_to_bucket_zero_rows():
    x = np.ones((3, 2, 2, 1), np.float32)
    padded, n, b = pad_to_bucket(x, (1, 2, 4))
    assert (n, b, padded.shape[0]) == (3, 4, 4)
    np.testing.assert_array_equal(padded[:3], x)
    assert (padded[3:] == 0).all()
    same, n, b = pad_to_bucket(x[:2], (1, 2, 4))
    assert same.shape[0] == 2 and b == 2


# ---------------------------------------------------------------------------
# incremental NCM / PrototypeStore (satellite: bit-for-bit coverage)
# ---------------------------------------------------------------------------
def test_store_single_shot_bitforbit():
    rng = np.random.default_rng(1)
    f = rng.normal(size=(3, 8)).astype(np.float32)
    labs = np.array([0, 1, 2], np.int32)
    store = PrototypeStore()
    for i, c in enumerate(("a", "b", "c")):
        assert store.register(c, f[i]) == 1          # 1-D single shot
    means, ids = store.prototypes()
    assert ids == ("a", "b", "c")
    offline = np.asarray(ncm.class_means(jnp.asarray(f), jnp.asarray(labs), 3))
    np.testing.assert_array_equal(means, offline)


def test_store_imbalanced_chunked_interleaved_bitforbit():
    """Chunked arrival interleaved ACROSS classes, imbalanced counts (7/1/3):
    per-class fold order is all that matters, so the store must equal one
    offline batch recompute over the concatenated support set exactly."""
    rng = np.random.default_rng(2)
    f = rng.normal(size=(11, 16)).astype(np.float32)
    labs = np.array([0] * 7 + [1] * 1 + [2] * 3, np.int32)
    store = PrototypeStore()
    store.register("a", f[0:3])
    store.register("c", f[8:9])
    store.register("a", f[3:7])
    store.register("b", f[7:8])
    store.register("c", f[9:11])
    assert store.counts() == {"a": 7, "b": 1, "c": 3}
    means, ids = store.prototypes()
    offline = np.asarray(ncm.class_means(jnp.asarray(f), jnp.asarray(labs), 3))
    idx = {c: i for i, c in enumerate(ids)}
    np.testing.assert_array_equal(
        means[[idx["a"], idx["b"], idx["c"]]], offline)


def test_store_classify_matches_offline_ncm():
    rng = np.random.default_rng(3)
    f = rng.normal(size=(10, 8)).astype(np.float32)
    labs = np.asarray(rng.integers(0, 4, 10), np.int32)
    store = PrototypeStore()
    for c in range(4):
        rows = f[labs == c]
        if len(rows):
            store.register(c, rows)
    q = rng.normal(size=(6, 8)).astype(np.float32)
    means = ncm.class_means(jnp.asarray(f[np.argsort(labs, kind="stable")]),
                            jnp.asarray(np.sort(labs)), 4)
    want = np.asarray(ncm.ncm_classify(jnp.asarray(q), means))
    ids, sims = store.classify(q)
    assert sims.shape == (6, len(store))
    assert [store.class_ids[i] for i in sims.argmax(-1)] == ids
    np.testing.assert_array_equal(np.asarray(ids), want)


def test_store_errors():
    store = PrototypeStore()
    with pytest.raises(RuntimeError):
        store.prototypes()
    store.register("a", np.ones((2, 4), np.float32))
    with pytest.raises(ValueError):
        store.register("a", np.ones((2, 5), np.float32))   # dim mismatch
    with pytest.raises(ValueError):
        store.register("b", np.zeros((0, 4), np.float32))  # empty chunk
    store.reset()
    assert len(store) == 0


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
def test_registry_default_and_hot_swap():
    reg = ArtifactRegistry()
    with pytest.raises(KeyError):
        reg.get()
    a = reg.register("a", lambda x: x)
    reg.register("b", lambda x: x)
    assert reg.default_name == "a" and reg.get() is a
    reg.set_default("b")
    assert reg.get().name == "b"
    with pytest.raises(KeyError):
        reg.set_default("nope")
    with pytest.raises(KeyError):
        reg.get("nope")
    # re-register replaces atomically; register(default=True) swaps
    reg.register("a", lambda x: x + 1, default=True)
    assert reg.default_name == "a" and reg.get("a").feats(1) == 2
    assert reg.names() == ("a", "b") and len(reg) == 2


def test_registry_stores_are_per_artifact():
    reg = ArtifactRegistry()
    reg.register("x", lambda v: v)
    reg.register("y", lambda v: v)
    reg.get("x").store.register("c", np.ones((1, 4), np.float32))
    assert len(reg.get("x").store) == 1
    assert len(reg.get("y").store) == 0


# ---------------------------------------------------------------------------
# DeployedModel bucket cache (satellite: retrace-per-batch-shape fix)
# ---------------------------------------------------------------------------
def test_deployed_model_warmup_and_batched(served):
    pipe, params = served
    dm = repro.compile(params, QCFG, recipe="resnet9", datapath="int")
    assert dm.trace_count == 0
    with pytest.raises(RuntimeError):
        dm.batched(np.zeros((2, IMG, IMG, 3), np.float32))  # before warmup
    bs = dm.warmup([1, 2, 4, 8], example=jnp.zeros((1, IMG, IMG, 3)))
    assert bs == (1, 2, 4, 8) and dm.buckets == bs
    traced = dm.trace_count
    assert traced == 4                       # one trace per bucket, no more
    x = fake_quant(jax.random.uniform(jax.random.PRNGKey(1),
                                      (3, IMG, IMG, 3)), QCFG.act)
    y = dm.batched(x)
    assert y.shape[0] == 3
    assert dm.trace_count == traced          # 3 -> bucket 4, already warm
    np.testing.assert_array_equal(np.asarray(y), np.asarray(dm(x[:3])))
    t = dm.throughput(x, iters=1)
    assert t["batch"] == 3.0 and t["bucket"] == 4.0
    with pytest.raises(ValueError):
        dm.batched(np.zeros((9, IMG, IMG, 3), np.float32))  # > max bucket
    # throughput past the largest bucket still measures (jit takes any
    # shape); it just reports the unbucketed batch as its own shape
    t9 = dm.throughput(jnp.zeros((9, IMG, IMG, 3)), iters=1)
    assert t9["batch"] == 9.0 and t9["bucket"] == 9.0
    with pytest.raises(ValueError):
        dm.warmup([2.5], example=jnp.zeros((1, IMG, IMG, 3)))  # float bucket


def test_pipeline_deploy_memoized(served):
    pipe, params = served
    f1 = pipe.deploy(params, datapath="int")
    assert pipe.deploy(params, datapath="int") is f1
    assert pipe.deploy(params, datapath="f32") is not f1
    other = jax.tree_util.tree_map(lambda v: v, params)
    assert pipe.deploy(other, datapath="int") is not f1


def test_pipeline_deploy_cache_is_bounded():
    """The memo is an LRU: deploy-after-update loops must not pin every
    historical param tree + artifact (one compiled model per step)."""
    pipe = FSLPipeline(width=WIDTH, qcfg=QCFG, deploy_cache_size=1)
    p1 = resnet9.init_params(jax.random.PRNGKey(1), WIDTH)
    p2 = resnet9.init_params(jax.random.PRNGKey(2), WIDTH)
    f1 = pipe.deploy(p1, datapath="f32")
    assert pipe.deploy(p2, datapath="f32") is not f1
    assert len(pipe._deploy_cache) == 1              # p1's entry evicted
    assert pipe.deploy(p1, datapath="f32") is not f1  # recompiled, not stale


def test_pipeline_deploy_warmup_stops_retraces(served):
    pipe, params = served
    feats = pipe.deploy(params, datapath="int")
    feats.warmup([1, 2, 4], img=IMG)
    t0 = feats.trace_count()
    for n in (1, 2, 4, 2, 1):
        out = feats(jnp.zeros((n, IMG, IMG, 3), jnp.float32))
        assert out.shape == (n, resnet9.feature_dim(WIDTH))
    assert feats.trace_count() == t0


# ---------------------------------------------------------------------------
# ServeEngine
# ---------------------------------------------------------------------------
def _engine(pipe, params, **kw):
    reg = ArtifactRegistry()
    reg.register("int", pipe.deploy(params, datapath="int"), default=True)
    kw.setdefault("max_batch", 8)
    kw.setdefault("batch_wait_ms", 1.0)
    return ServeEngine(reg, **kw)


def test_engine_mixed_traffic_bitforbit(served):
    """Registers + classifies through the engine == offline NCM on the same
    shots: prototypes bit-for-bit, predictions identical."""
    pipe, params = served
    rng = np.random.default_rng(7)
    shots = {f"cls{c}": _frames(rng, 2 + c) for c in range(3)}
    queries = _frames(rng, 5)
    with _engine(pipe, params) as eng:
        base = eng.warmup(img=IMG)
        futs = [eng.submit_register(c, x) for c, x in shots.items()]
        assert [f.result(60) for f in futs] == [2, 3, 4]
        res = eng.submit_classify(queries).result(60)
        # zero backbone retraces; the NCM head traces at most once more,
        # for the class count registered after warmup
        after, head = eng.trace_counts(), ServeEngine.HEAD_TRACES
        assert {**after, head: base[head]} == base
        assert after[head] - base[head] <= 1
        snap = eng.metrics.snapshot()
        assert snap["completed"] == 4 and snap["failed"] == 0
    feats = pipe.deploy(params, datapath="int")
    sup = np.concatenate([np.asarray(feats(jnp.asarray(x)))
                          for x in shots.values()])
    labs = np.concatenate([[c] * (2 + c) for c in range(3)]).astype(np.int32)
    offline = np.asarray(ncm.class_means(jnp.asarray(sup), jnp.asarray(labs),
                                         3))
    reg = eng.registry.get("int")
    means, ids = reg.store.prototypes()
    assert ids == tuple(shots)
    np.testing.assert_array_equal(means, offline)
    qf = np.asarray(feats(jnp.asarray(queries)))
    want = np.asarray(ncm.ncm_classify(jnp.asarray(qf), jnp.asarray(offline)))
    assert res.class_ids == [f"cls{p}" for p in want]
    assert res.artifact == "int" and res.sims.shape == (5, 3)


def test_engine_classify_before_register_fails_future(served):
    pipe, params = served
    with _engine(pipe, params) as eng:
        fut = eng.submit_classify(_frames(np.random.default_rng(0), 1))
        with pytest.raises(RuntimeError, match="no classes"):
            fut.result(60)
        assert eng.metrics.snapshot()["failed"] == 1


def test_engine_backpressure_rejects_when_full(served):
    pipe, params = served
    rng = np.random.default_rng(0)
    eng = _engine(pipe, params, max_queue=2, start=False)
    eng.submit_classify(_frames(rng, 1))
    eng.submit_classify(_frames(rng, 1))
    with pytest.raises(ServeOverload):
        eng.submit_classify(_frames(rng, 1))
    assert eng.metrics.snapshot()["rejected"] == 1
    eng.stop(drain=False)        # queued futures fail instead of hanging
    assert eng.metrics.snapshot()["failed"] == 2
    with pytest.raises(ServeOverload, match="stopped"):
        eng.submit_classify(_frames(rng, 1))   # no drain -> would hang


def test_engine_request_validation(served):
    pipe, params = served
    eng = _engine(pipe, params, start=False)
    with pytest.raises(ValueError):
        eng.submit_classify(np.zeros((IMG, IMG), np.float32))
    with pytest.raises(ValueError):        # single request > max_batch
        eng.submit_classify(np.zeros((9, IMG, IMG, 3), np.float32))
    eng.stop(drain=False)


def test_engine_unknown_artifact_fails_future(served):
    pipe, params = served
    with _engine(pipe, params) as eng:
        fut = eng.submit_classify(_frames(np.random.default_rng(0), 1),
                                  artifact="nope")
        with pytest.raises(KeyError):
            fut.result(60)


def test_engine_ab_artifacts_and_hot_swap(served):
    """Two bit-width artifacts served side by side: separate stores, and the
    registry default hot-swaps between batches."""
    pipe, params = served
    reg = ArtifactRegistry()
    reg.register("int", pipe.deploy(params, datapath="int"), default=True)
    reg.register("f32", pipe.deploy(params, datapath="f32"))
    rng = np.random.default_rng(11)
    shots0, shots1 = _frames(rng, 3), _frames(rng, 2)
    with ServeEngine(reg, max_batch=8, batch_wait_ms=1.0) as eng:
        eng.warmup(img=IMG)
        for art in ("int", "f32"):
            eng.submit_register("c0", shots0, artifact=art).result(60)
            eng.submit_register("c1", shots1, artifact=art).result(60)
        q = _frames(rng, 4)
        r_int = eng.submit_classify(q, artifact="int").result(60)
        r_f32 = eng.submit_classify(q, artifact="f32").result(60)
        assert r_int.artifact == "int" and r_f32.artifact == "f32"
        # int and f32 artifacts are bit-for-bit equal on the grid, so the
        # A/B pair must agree (the PR 2 exactness contract, now under serve)
        np.testing.assert_array_equal(r_int.sims, r_f32.sims)
        reg.set_default("f32")
        assert eng.submit_classify(q).result(60).artifact == "f32"


def test_engine_concurrent_submitters_fifo_per_class(served):
    """Many threads registering DISJOINT classes + classifying concurrently:
    per-class chunk order is per-thread sequential, so every class prototype
    must still be bit-for-bit vs that class's own shots."""
    pipe, params = served
    rng = np.random.default_rng(13)
    chunks = {t: [_frames(rng, 1 + (i % 3)) for i in range(4)]
              for t in range(4)}
    with _engine(pipe, params, max_queue=512) as eng:
        eng.warmup(img=IMG)

        def submit(tid):
            for ch in chunks[tid]:
                eng.submit_register(tid, ch).result(60)

        threads = [threading.Thread(target=submit, args=(t,))
                   for t in chunks]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        store = eng.registry.get("int").store
        feats = pipe.deploy(params, datapath="int")
        means, ids = store.prototypes()
        for tid, chs in chunks.items():
            sup = np.concatenate([np.asarray(feats(jnp.asarray(c)))
                                  for c in chs])
            labs = np.zeros((len(sup),), np.int32)
            offline = np.asarray(ncm.class_means(jnp.asarray(sup),
                                                 jnp.asarray(labs), 1))[0]
            np.testing.assert_array_equal(means[ids.index(tid)], offline)


def test_engine_survives_cancelled_future(served):
    """A client cancelling a queued future must not kill the worker (its
    set_result would raise InvalidStateError): later requests still serve,
    and the cancellation is counted."""
    pipe, params = served
    rng = np.random.default_rng(19)
    eng = _engine(pipe, params, start=False)
    doomed = eng.submit_classify(_frames(rng, 1))
    assert doomed.cancel()
    survivor = eng.submit_register("c0", _frames(rng, 2))
    eng.start()
    assert survivor.result(60) == 2
    after = eng.submit_classify(_frames(rng, 1)).result(60)
    assert after.class_ids == ["c0"]
    assert eng.metrics.snapshot()["cancelled"] == 1
    eng.stop()


def test_engine_warmup_bucket_override_replaces_set(served):
    """A warmup bucket override must become the padding set (warming a
    subset while padding to the old set would reintroduce retraces), and
    must still cover max_batch."""
    pipe, params = served
    eng = _engine(pipe, params, max_batch=8, start=False)
    with pytest.raises(ValueError):
        eng.warmup(img=IMG, buckets=[1, 2, 4])       # can't cover max_batch
    eng.warmup(img=IMG, buckets=[1, 8])
    assert eng.buckets == (1, 8)
    with pytest.raises(ValueError):
        ServeEngine(eng.registry, max_batch=8, buckets=[2.5, 8], start=False)
    eng.stop()


def test_engine_default_alias_keeps_arrival_order(served):
    """artifact=None and the default's explicit name are the SAME stream:
    a register addressed one way must be visible to a later classify
    addressed the other way even when they ride the same batch."""
    pipe, params = served
    rng = np.random.default_rng(23)
    eng = _engine(pipe, params, start=False)     # force one coalesced batch
    eng.submit_register("A", _frames(rng, 1))                # via default
    c1 = eng.submit_classify(_frames(rng, 1), artifact="int")
    eng.submit_register("B", _frames(rng, 1), artifact="int")
    c2 = eng.submit_classify(_frames(rng, 1))                # via default
    eng.start()
    assert c1.result(60).sims.shape == (1, 1)    # before B registered
    assert c2.result(60).sims.shape == (1, 2)    # after B registered
    eng.stop()


def test_engine_serves_raw_deployed_model(served):
    """A bare DeployedModel (no fused flip ensemble) is a valid artifact:
    the registry adapts its warmup/trace_count interface and the engine
    serves it with zero retraces."""
    pipe, params = served
    dm = repro.compile(params, QCFG, recipe="resnet9", datapath="int")
    reg = ArtifactRegistry()
    reg.register("raw", dm)
    rng = np.random.default_rng(17)
    with ServeEngine(reg, max_batch=8, batch_wait_ms=1.0) as eng:
        base = eng.warmup(img=IMG)
        assert base["raw"] == dm.trace_count
        eng.submit_register("c0", _frames(rng, 2)).result(60)
        eng.submit_register("c1", _frames(rng, 2)).result(60)
        res = eng.submit_classify(_frames(rng, 3)).result(60)
        assert len(res.class_ids) == 3 and res.artifact == "raw"
        after, head = eng.trace_counts(), ServeEngine.HEAD_TRACES
        assert {**after, head: base[head]} == base
        assert after[head] - base[head] <= 1         # one new class count


def test_metrics_percentiles_and_counters():
    from repro.serve.metrics import ServeMetrics, percentile
    assert np.isnan(percentile([], 50))
    assert percentile([1.0, 2.0, 3.0, 4.0], 0) == 1.0
    assert percentile([1.0, 2.0, 3.0, 4.0], 100) == 4.0
    m = ServeMetrics(window=4)
    for v in (0.1, 0.2, 0.3, 0.4, 0.5):      # reservoir drops the oldest
        m.record_request(v)
    m.record_batch(3, 4)
    m.observe_queue_depth(7)
    s = m.snapshot()
    assert s["completed"] == 5 and s["p50_ms"] == pytest.approx(400.0)
    assert s["mean_batch"] == 3.0 and s["padded_frac"] == 0.25
    assert s["max_queue_depth"] == 7
    assert "p95" in m.report()


# ---------------------------------------------------------------------------
# percentile math on tiny/empty windows + stop idempotency + registry meta
# (ISSUE 4 satellites: previously untested paths, behavior locked here)
# ---------------------------------------------------------------------------
def test_percentile_empty_window_is_nan_everywhere():
    from repro.serve.metrics import ServeMetrics, percentile
    for p in (0, 50, 95, 99, 100):
        assert np.isnan(percentile([], p))
    s = ServeMetrics().snapshot()                    # no traffic at all
    assert np.isnan(s["p50_ms"]) and np.isnan(s["p99_ms"])
    assert np.isnan(s["mean_batch"]) and s["throughput_rps"] == 0.0


def test_percentile_single_sample_window():
    """n=1: every percentile is THE sample (nearest rank on one rank)."""
    from repro.serve.metrics import percentile
    for p in (0, 50, 95, 99, 100):
        assert percentile([7.5], p) == 7.5


def test_percentile_two_sample_window_nearest_rank():
    """n=2 locks the nearest-rank rounding: k = round(p/100), and Python's
    round-half-even sends p50 to the LOWER sample — a deliberate
    (conservative-for-latency) property a future 'fix' must not silently
    flip."""
    from repro.serve.metrics import percentile
    assert percentile([1.0, 9.0], 50) == 1.0         # round(0.5) == 0
    assert percentile([1.0, 9.0], 51) == 9.0
    assert percentile([1.0, 9.0], 95) == 9.0
    assert percentile([1.0, 9.0], 99) == 9.0


def test_percentile_clamps_out_of_range_p():
    from repro.serve.metrics import percentile
    vals = [1.0, 2.0, 3.0]
    assert percentile(vals, -10) == 1.0              # k clamped to 0
    assert percentile(vals, 250) == 3.0              # k clamped to n-1


def test_engine_stop_is_idempotent(served):
    """stop() on a running, stopped, or never-started engine is safe; a
    stop→start→stop cycle serves in between; submits after the final stop
    are rejected (not hung)."""
    pipe, params = served
    rng = np.random.default_rng(5)
    eng = _engine(pipe, params, start=False)
    eng.stop()                                       # never started: no-op
    eng.stop()
    eng.start()
    eng.submit_register("c", _frames(rng, 2)).result(timeout=60)
    eng.stop()
    eng.stop()                                       # second stop: no-op
    with pytest.raises(ServeOverload, match="stopped"):
        eng.submit_classify(_frames(rng, 1))
    eng.start()                                      # restart still works
    res = eng.submit_classify(_frames(rng, 1)).result(timeout=60)
    assert res.class_ids == ["c"]
    eng.stop()


def test_engine_stop_drain_false_twice(served):
    """drain=False on an already-stopped engine must not throw while
    failing an empty queue."""
    pipe, params = served
    eng = _engine(pipe, params)
    eng.stop(drain=False)
    eng.stop(drain=False)


def test_registry_register_attaches_metadata():
    reg = ArtifactRegistry()
    reg.register("a", lambda x: x, meta={"weight_bytes": 123, "knee": True})
    reg.register("b", lambda x: x)
    assert reg.get("a").meta["weight_bytes"] == 123
    assert reg.get("b").meta == {}
    md = reg.metadata()
    assert md["a"]["knee"] and md["b"] == {}
    md["a"]["knee"] = False                          # copies: no write-through
    assert reg.get("a").meta["knee"]


# ---------------------------------------------------------------------------
# soak (slow): the ISSUE 3 acceptance scenario
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_soak_1000_mixed_requests_zero_retrace(served):
    """>= 1000 mixed register/classify requests under concurrent load:
    ZERO retraces after warmup, queue depth bounded, nothing rejected or
    failed, and the final prototypes bit-for-bit equal to an offline NCM
    recompute over every registered shot in arrival order."""
    pipe, params = served
    rng = np.random.default_rng(42)
    n_req, n_classes = 1000, 8
    plan = []                    # (kind, class, frames) fixed up front
    for i in range(n_req):
        if i < n_classes or rng.random() < 0.15:
            c = i % n_classes if i < n_classes else int(rng.integers(n_classes))
            plan.append(("register", c, _frames(rng, int(rng.integers(1, 5)))))
        else:
            plan.append(("classify", None, _frames(rng, int(rng.integers(1, 4)))))
    with _engine(pipe, params, max_batch=32, max_queue=256,
                 batch_wait_ms=1.0) as eng:
        base = eng.warmup(img=IMG)
        futs = []
        for kind, c, x in plan:
            if kind == "register":
                futs.append(eng.submit_register(c, x, timeout=30.0))
            else:
                futs.append(eng.submit_classify(x, timeout=30.0))
        results = [f.result(timeout=120) for f in futs]
        assert len(results) == n_req
        # the backbone never retraces; the head does once per new class
        # count, which the interleaved registers keep changing
        head = ServeEngine.HEAD_TRACES
        assert {**eng.trace_counts(), head: base[head]} == base, \
            "retraced under steady-state load"
        snap = eng.metrics.snapshot()
        assert snap["completed"] == n_req
        assert snap["rejected"] == 0 and snap["failed"] == 0
        assert 1 < snap["max_queue_depth"] <= 256    # batching actually queued
        assert snap["mean_batch"] > 2.0              # coalescing actually ran
        assert snap["p99_ms"] > 0
        store = eng.registry.get("int").store
    # offline recompute: every registered chunk, per class, in arrival order
    feats = pipe.deploy(params, datapath="int")
    by_class = {}
    for kind, c, x in plan:
        if kind == "register":
            by_class.setdefault(c, []).append(x)
    means, ids = store.prototypes()
    for c, chunks in by_class.items():
        sup = np.concatenate([np.asarray(feats(jnp.asarray(ch)))
                              for ch in chunks])
        offline = np.asarray(ncm.class_means(
            jnp.asarray(sup), jnp.zeros((len(sup),), jnp.int32), 1))[0]
        np.testing.assert_array_equal(means[ids.index(c)], offline)
