"""repro.serve.cluster — multi-tenant sharded serving cluster with a
persistent AOT compile cache (ISSUE 6).

Covers: graph fingerprinting, the CompileCache round trip (save → evict
from memory → restore → bit-for-bit vs a fresh trace) and its clean-miss
discipline on corrupt entries, DeployedModel warmup through the cache
(zero traces on restore), TenantRegistry namespacing + store isolation,
per-tenant admission quotas (TenantOverQuota, not generic overload), the
sharded NCM head's serial fallback and multi-device bitwise equality, the
ServeCluster end to end with a cold restart, and (slow) a 1000-request
multi-tenant soak with zero retraces after cache restore.
"""

import copy
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.ckpt import CompileCache, graph_fingerprint
from repro.core.quant import QuantConfig, fake_quant
from repro.fsl import ncm
from repro.fsl.pipeline import FSLPipeline
from repro.models import resnet9
from repro.serve import (
    ArtifactRegistry,
    PrototypeStore,
    ServeEngine,
    ServeMetrics,
    ServeOverload,
)
from repro.serve.cluster import (
    ServeCluster,
    ShardedNCMHead,
    ShardedStore,
    TenantOverQuota,
    TenantRegistry,
    sharded_tenant_registry,
)

WIDTH, IMG = 4, 16
QCFG = QuantConfig.paper_w6a4()


@pytest.fixture(scope="module")
def served():
    """One param set + pipeline shared by the cluster tests."""
    params = resnet9.init_params(jax.random.PRNGKey(0), WIDTH)
    pipe = FSLPipeline(width=WIDTH, qcfg=QCFG)
    return pipe, params


@pytest.fixture(scope="module")
def deployed(served):
    """One compiled int DeployedModel for the fingerprint/cache tests."""
    _, params = served
    return repro.compile(params, QCFG, recipe="resnet9", datapath="int")


def _frames(rng, n):
    return rng.random((n, IMG, IMG, 3)).astype(np.float32)


def _flat_feats(x):
    # cheap backbone stand-in for engine-mechanics tests: no compile needed
    return np.asarray(x, np.float32).reshape(len(x), -1)


# ---------------------------------------------------------------------------
# graph fingerprint (cache-identity half of the key)
# ---------------------------------------------------------------------------
def test_graph_fingerprint_stable_and_name_free(deployed):
    fp = graph_fingerprint(deployed.graph)
    assert fp == graph_fingerprint(deployed.graph)       # deterministic
    renamed = copy.deepcopy(deployed.graph)
    renamed.name = "totally-different-name"
    assert graph_fingerprint(renamed) == fp              # name excluded


def test_graph_fingerprint_sees_initializer_bytes(deployed):
    g = copy.deepcopy(deployed.graph)
    name = sorted(g.initializers)[0]
    arr = np.array(g.initializers[name], copy=True)
    arr.flat[0] = arr.flat[0] + 1                        # one weight byte
    g.initializers[name] = arr
    assert graph_fingerprint(g) != graph_fingerprint(deployed.graph)


def test_deployed_fingerprint_includes_datapath(served, deployed):
    """Fingerprint format: <graph-hash>-<datapath>-<pass-set-digest> (the
    pass digest is the PR 7 stale-cache fix — builds that differ only in
    the fuse pass must never alias one persisted executable)."""
    _, params = served
    dm_f32 = repro.compile(params, QCFG, recipe="resnet9", datapath="f32")
    assert deployed.fingerprint().split("-")[1] == "int"
    assert dm_f32.fingerprint().split("-")[1] == "f32"
    assert deployed.fingerprint() != dm_f32.fingerprint()


# ---------------------------------------------------------------------------
# CompileCache (tentpole layer 3): round trip, misses, corruption
# ---------------------------------------------------------------------------
def test_compile_cache_roundtrip_bitforbit(tmp_path):
    """save → evict (fresh cache object, nothing in memory) → restore →
    outputs bit-for-bit equal to the freshly traced executable."""
    cache = CompileCache(str(tmp_path))
    x = jnp.arange(8, dtype=jnp.float32)
    compiled = jax.jit(lambda v: jnp.sin(v) * 2.0 + v).lower(x).compile()
    key = cache.key(kind="test", shape=[8])
    cache.store(key, compiled)
    assert cache.has(key) and key in cache.keys()
    restored = CompileCache(str(tmp_path)).load(key)     # cold process stand-in
    assert restored is not None
    np.testing.assert_array_equal(np.asarray(restored(x)),
                                  np.asarray(compiled(x)))
    cache.evict(key)
    assert not cache.has(key)
    assert cache.load(key) is None
    st = cache.stats()
    assert st["stores"] == 1 and st["misses"] == 1 and st["entries"] == 0


def test_compile_cache_get_or_compile_counts(tmp_path):
    cache = CompileCache(str(tmp_path))
    x = jnp.zeros((4,), jnp.float32)
    fn = jax.jit(lambda v: v + 1)
    calls = []

    def compile_fn():
        calls.append(1)
        return fn.lower(x).compile()

    key = cache.key(kind="goc")
    exe1, hit1, s1 = cache.get_or_compile(key, compile_fn)
    assert not hit1 and len(calls) == 1 and s1 > 0
    exe2, hit2, _ = cache.get_or_compile(key, compile_fn)
    assert hit2 and len(calls) == 1                      # no second compile
    np.testing.assert_array_equal(np.asarray(exe1(x)), np.asarray(exe2(x)))
    assert cache.stats() == {"hits": 1, "misses": 1, "stores": 1,
                             "load_errors": 0, "entries": 1}


def test_compile_cache_keys_are_content_sensitive(tmp_path):
    cache = CompileCache(str(tmp_path))
    assert cache.key(a=1) == cache.key(a=1)
    assert cache.key(a=1) != cache.key(a=2)
    assert cache.key(a=1) != cache.key(a=1, b=0)


def test_compile_cache_corrupt_entry_is_clean_miss(tmp_path):
    """A present-but-unloadable entry must load as None (evicted, counted)
    — the cache may only make cold start faster, never wronger."""
    import os

    cache = CompileCache(str(tmp_path))
    x = jnp.zeros((2,), jnp.float32)
    key = cache.key(kind="corrupt")
    cache.store(key, jax.jit(lambda v: v * 3).lower(x).compile())
    entry_dir = cache.mgr._named_dir(key)
    for fname in os.listdir(entry_dir):
        with open(os.path.join(entry_dir, fname), "wb") as f:
            f.write(b"not an executable")
    assert cache.load(key) is None
    st = cache.stats()
    assert st["load_errors"] == 1 and st["misses"] == 1
    assert not cache.has(key)                            # evicted on failure


# ---------------------------------------------------------------------------
# DeployedModel warmup through the cache (tier-1 cold-start smoke)
# ---------------------------------------------------------------------------
def test_deployed_warmup_cache_restore_zero_trace_bitforbit(served, tmp_path):
    """Cold warmup publishes executables; a fresh compile of the same params
    warms purely from the cache with ZERO traces and serves bit-for-bit
    identical outputs."""
    _, params = served
    cache = CompileCache(str(tmp_path))
    dm1 = repro.compile(params, QCFG, recipe="resnet9", datapath="int")
    ex = jnp.zeros((1, IMG, IMG, 3), jnp.float32)
    dm1.warmup([1, 2], example=ex, cache=cache)
    assert dm1.trace_count == 2                          # one per bucket
    assert [e["cached"] for e in dm1.compile_log] == [False, False]
    assert cache.stats()["stores"] == 2
    x = fake_quant(jax.random.uniform(jax.random.PRNGKey(3),
                                      (2, IMG, IMG, 3)), QCFG.act)
    want = np.asarray(dm1(x))

    dm2 = repro.compile(params, QCFG, recipe="resnet9", datapath="int")
    assert dm2.fingerprint() == dm1.fingerprint()
    metrics = ServeMetrics()
    dm2.warmup([1, 2], example=ex, cache=cache, metrics=metrics, label="dm2")
    assert dm2.trace_count == 0                          # pure restore
    assert [e["cached"] for e in dm2.compile_log] == [True, True]
    np.testing.assert_array_equal(np.asarray(dm2(x)), want)
    np.testing.assert_array_equal(np.asarray(dm2.batched(x[:1])), want[:1])
    assert dm2.trace_count == 0                          # still never traced
    cs = metrics.compile_snapshot()
    assert cs["compile_events"] == 2 and cs["compile_cached"] == 2
    assert cs["compile_fresh_s"] == 0.0                  # nothing compiled
    # re-warming an already-warm bucket set is a no-op (shared artifacts)
    dm2.warmup([1, 2], example=ex, cache=cache)
    assert len(dm2.compile_log) == 2


def test_pipeline_deploy_warmup_cache_restore(served, tmp_path):
    """Same contract for the fused flip-ensemble feats the engine serves."""
    _, params = served
    cache = CompileCache(str(tmp_path))
    f1 = FSLPipeline(width=WIDTH, qcfg=QCFG).deploy(params, datapath="int")
    f1.warmup([1, 2], img=IMG, cache=cache)
    x = jnp.zeros((2, IMG, IMG, 3), jnp.float32)
    want = np.asarray(f1(x))
    f2 = FSLPipeline(width=WIDTH, qcfg=QCFG).deploy(params, datapath="int")
    assert f2 is not f1
    f2.warmup([1, 2], img=IMG, cache=cache)
    assert f2.trace_count() == 0                         # restored, not traced
    np.testing.assert_array_equal(np.asarray(f2(x)), want)
    assert f2.trace_count() == 0
    assert cache.stats()["hits"] == 2 and cache.stats()["stores"] == 2


# ---------------------------------------------------------------------------
# TenantRegistry (tentpole layer 1): namespaces, isolation, defaults
# ---------------------------------------------------------------------------
def test_tenant_registry_namespacing_and_isolation():
    reg = TenantRegistry()
    with pytest.raises(ValueError):
        reg.add_tenant("early")                          # no backbone yet
    feats = _flat_feats
    reg.register_backbone("bb", feats, default=True)
    reg.add_tenant("acme")
    reg.add_tenant("acme")                               # idempotent
    reg.add_tenant("bob")
    assert reg.resolve("acme") == "acme/bb"
    assert reg.resolve("acme", "bb") == "acme/bb"
    assert reg.get("acme/bb").feats is feats             # shared backbone
    assert reg.get("bob/bb").feats is feats
    # private stores: acme's class invisible to bob and to the bare backbone
    reg.tenant_store("acme").register("c", np.ones((1, 4), np.float32))
    assert len(reg.tenant_store("acme")) == 1
    assert len(reg.tenant_store("bob")) == 0
    assert len(reg.get("bb").store) == 0
    assert reg.tenants() == ("acme", "bob")
    assert reg.backbone_names() == ("bb",)


def test_tenant_registry_unknown_names_raise():
    reg = TenantRegistry()
    reg.register_backbone("bb", _flat_feats, default=True)
    reg.add_tenant("acme")
    with pytest.raises(KeyError):
        reg.resolve("ghost")                             # never auto-created
    with pytest.raises(KeyError):
        reg.resolve("acme", "nope")
    with pytest.raises(KeyError):
        reg.add_tenant("z", default_backbone="nope")
    with pytest.raises(ValueError):
        reg.add_tenant("bad/name")                       # separator reserved
    with pytest.raises(ValueError):
        reg.register_backbone("a/b", _flat_feats)
    with pytest.raises(ValueError):
        reg.add_tenant("")


def test_tenant_registry_backbone_after_tenant_and_default_swap():
    reg = TenantRegistry()
    reg.register_backbone("w6", _flat_feats, default=True)
    reg.add_tenant("acme")
    reg.register_backbone("w4", _flat_feats)             # late backbone
    assert reg.resolve("acme", "w4") == "acme/w4"        # view auto-created
    assert reg.resolve("acme") == "acme/w6"
    reg.set_tenant_default("acme", "w4")                 # per-tenant A/B swap
    assert reg.resolve("acme") == "acme/w4"
    with pytest.raises(KeyError):
        reg.set_tenant_default("acme", "nope")


# ---------------------------------------------------------------------------
# per-tenant admission quotas (satellite: TenantOverQuota, not overload)
# ---------------------------------------------------------------------------
def _quota_engine(**kw):
    reg = ArtifactRegistry()
    reg.register("bb", _flat_feats, default=True)
    kw.setdefault("max_batch", 8)
    return ServeEngine(reg, start=False, **kw)


def test_tenant_quota_rejects_only_the_offender():
    eng = _quota_engine(max_queue=8, tenant_quota=2)
    x = np.zeros((1, 4, 4, 3), np.float32)
    eng.submit_classify(x, tenant="noisy")
    eng.submit_classify(x, tenant="noisy")
    with pytest.raises(TenantOverQuota):
        eng.submit_classify(x, tenant="noisy")
    assert issubclass(TenantOverQuota, ServeOverload)    # still sheddable
    eng.submit_classify(x, tenant="victim")              # others admitted
    eng.submit_classify(x)                               # untenanted bypasses
    snap = eng.metrics.snapshot()
    assert snap["rejected"] == 1 and snap["over_quota"] == 1
    ts = eng.metrics.tenant_snapshot()
    assert ts["noisy"]["over_quota"] == 1 and ts["noisy"]["rejected"] == 1
    assert "victim" not in ts                            # nothing to report yet
    assert eng.tenant_queue_depths() == {"noisy": 2, "victim": 1}
    eng.stop(drain=False)
    assert eng.tenant_queue_depths() == {}               # released on failure
    assert eng.metrics.tenant_snapshot()["victim"]["failed"] == 1


def test_tenant_quota_rejection_keeps_shared_queue_free():
    """An over-quota tenant must not consume shared-queue capacity: after
    its rejection the queue still admits max_queue more requests."""
    eng = _quota_engine(max_queue=3, tenant_quota=1)
    x = np.zeros((1, 4, 4, 3), np.float32)
    eng.submit_classify(x, tenant="noisy")
    for _ in range(5):
        with pytest.raises(TenantOverQuota):
            eng.submit_classify(x, tenant="noisy")
    eng.submit_classify(x, tenant="a")
    eng.submit_classify(x, tenant="b")                   # queue fills to 3
    with pytest.raises(ServeOverload) as exc:
        eng.submit_classify(x, tenant="c")               # shared queue full
    assert not isinstance(exc.value, TenantOverQuota)    # distinct failure
    eng.stop(drain=False)


def test_tenant_quota_normalization_and_validation():
    assert _quota_engine(max_queue=8, tenant_quota=0.25).tenant_quota == 2
    assert _quota_engine(max_queue=8, tenant_quota=1.0).tenant_quota == 8
    assert _quota_engine(max_queue=8, tenant_quota=3).tenant_quota == 3
    assert _quota_engine(max_queue=8, tenant_quota=0.01).tenant_quota == 1
    assert _quota_engine(max_queue=8).tenant_quota is None
    for bad in (0, -1, 0.0, 1.5, -0.5, "half"):
        with pytest.raises(ValueError):
            _quota_engine(max_queue=8, tenant_quota=bad)


def test_tenant_quota_releases_as_requests_serve():
    """Quota counts QUEUED requests: a tenant at quota regains its share as
    the worker drains, so steady sequential traffic never rejects."""
    reg = ArtifactRegistry()
    reg.register("bb", _flat_feats, default=True)
    with ServeEngine(reg, max_batch=4, max_queue=8, tenant_quota=1,
                     batch_wait_ms=1.0) as eng:
        for i in range(5):
            x = np.full((1, 2, 2, 1), float(i), np.float32)
            assert eng.submit_register("c", x, tenant="t").result(60) == i + 1
        snap = eng.metrics.snapshot()
        assert snap["over_quota"] == 0 and snap["rejected"] == 0
        assert eng.metrics.tenant_snapshot()["t"]["completed"] == 5
        assert eng.tenant_queue_depths() == {}


# ---------------------------------------------------------------------------
# sharded NCM head (tentpole layer 2)
# ---------------------------------------------------------------------------
def test_sharded_head_single_device_serial_fallback():
    head = ShardedNCMHead()
    assert head.mesh is None and head.n_dev == 1         # 1 device -> serial
    rng = np.random.default_rng(4)
    q = rng.normal(size=(5, 8)).astype(np.float32)
    m = rng.normal(size=(3, 8)).astype(np.float32)
    want = np.asarray(jax.jit(ncm.cosine_sims)(q, m))
    np.testing.assert_array_equal(head.sims(q, m), want)
    assert head.sims(q, np.zeros((0, 8), np.float32)).shape == (5, 0)


def test_sharded_store_matches_plain_store_bitforbit():
    rng = np.random.default_rng(6)
    f = rng.normal(size=(10, 8)).astype(np.float32)
    plain, sharded = PrototypeStore(), ShardedStore(ShardedNCMHead())
    for cid in range(5):
        plain.register(cid, f[2 * cid:2 * cid + 2])
        sharded.register(cid, f[2 * cid:2 * cid + 2])
    q = rng.normal(size=(4, 8)).astype(np.float32)
    ids_p, sims_p = plain.classify(q)
    ids_s, sims_s = sharded.classify(q)
    assert ids_p == ids_s
    np.testing.assert_array_equal(sims_p, sims_s)
    ids1, sims1 = sharded.classify(q[0])                 # 1-D query promotion
    assert ids1 == [ids_s[0]] and sims1.shape == (1, 5)


def test_sharded_tenant_registry_shares_one_head():
    reg = sharded_tenant_registry()
    reg.register_backbone("bb", _flat_feats, default=True)
    reg.add_tenant("t1")
    reg.add_tenant("t2")
    s1, s2 = reg.tenant_store("t1"), reg.tenant_store("t2")
    assert isinstance(s1, ShardedStore) and isinstance(s2, ShardedStore)
    assert s1 is not s2 and s1.head is s2.head           # state private,
    assert reg.get("bb").store.head is s1.head           # compute shared


def test_sharded_head_multidevice_bitforbit():
    """4 forced host devices: shard_map head == serial head bit-for-bit,
    including padded (non-divisible) prototype counts, and the sharded
    store == plain store through classify."""
    from test_multidevice import run_py

    out = run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.serve.cluster import ShardedNCMHead, ShardedStore
        from repro.serve.store import PrototypeStore
        from repro.fsl import ncm
        assert len(jax.devices()) == 4
        head = ShardedNCMHead()
        assert head.mesh is not None and head.n_dev == 4
        rng = np.random.default_rng(0)
        q = rng.normal(size=(6, 16)).astype(np.float32)
        serial = jax.jit(ncm.cosine_sims)
        for c in (1, 3, 4, 8, 11):          # divisible AND padded cases
            m = rng.normal(size=(c, 16)).astype(np.float32)
            got = head.sims(q, m)
            want = np.asarray(serial(jnp.asarray(q), jnp.asarray(m)))
            assert got.shape == (6, c)
            np.testing.assert_array_equal(got, want)
        plain, shard = PrototypeStore(), ShardedStore(head)
        f = rng.normal(size=(10, 16)).astype(np.float32)
        for cid in range(5):
            plain.register(cid, f[2*cid:2*cid+2])
            shard.register(cid, f[2*cid:2*cid+2])
        i1, s1 = plain.classify(q)
        i2, s2 = shard.classify(q)
        assert i1 == i2
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
        print("SHARDED_OK")
    """, devices=4)
    assert "SHARDED_OK" in out


# ---------------------------------------------------------------------------
# ServeCluster end to end + cold restart (the acceptance scenario)
# ---------------------------------------------------------------------------
def test_cluster_end_to_end_and_cold_restart(served, tmp_path):
    pipe, params = served
    cache = CompileCache(str(tmp_path / "exec"))
    rng = np.random.default_rng(9)

    def build_registry():
        # a fresh pipeline per "process": nothing warm in memory
        reg = TenantRegistry()
        feats = FSLPipeline(width=WIDTH, qcfg=QCFG).deploy(params,
                                                           datapath="int")
        reg.register_backbone("w6a4-int", feats, default=True)
        return reg

    reg = build_registry()
    shots = {f"cls{c}": _frames(rng, 2) for c in range(2)}
    queries = _frames(rng, 3)
    with ServeCluster(reg, replicas=2, max_batch=4, batch_wait_ms=1.0,
                      tenant_quota=0.5, compile_cache=cache) as cluster:
        cluster.add_tenant("acme")
        cluster.add_tenant("bob")
        base = cluster.warmup(img=IMG)
        for c, x in shots.items():
            assert cluster.submit_register("acme", c, x).result(60) == 2
        res = cluster.submit_classify("acme", queries).result(60)
        assert res.artifact == "acme/w6a4-int"
        assert len(res.class_ids) == 3 and res.sims.shape == (3, 2)
        # bob's namespace is isolated: nothing registered there
        with pytest.raises(RuntimeError, match="no classes"):
            cluster.submit_classify("bob", _frames(rng, 1)).result(60)
        with pytest.raises(KeyError):
            cluster.submit_classify("ghost", _frames(rng, 1))
        assert cluster.trace_counts() == base            # zero retraces
        snap = cluster.metrics_snapshot()
        assert snap["tenants"]["acme"]["completed"] == 3
        assert snap["tenants"]["bob"]["failed"] == 1
        assert snap["completed"] == 3 and snap["over_quota"] == 0
        assert snap["compile_s"] > 0
        store = reg.tenant_store("acme")

    # tenant prototypes bit-for-bit vs offline NCM over acme's shots alone
    feats = pipe.deploy(params, datapath="int")
    sup = np.concatenate([np.asarray(feats(jnp.asarray(x)))
                          for x in shots.values()])
    labs = np.repeat(np.arange(2), 2).astype(np.int32)
    offline = np.asarray(ncm.class_means(jnp.asarray(sup), jnp.asarray(labs),
                                         2))
    means, ids = store.prototypes()
    assert ids == tuple(shots)
    np.testing.assert_array_equal(means, offline)
    want_ids = list(res.class_ids)

    # -- cold restart: fresh registry/pipeline, warm purely from the cache --
    stores_before = cache.stats()["stores"]
    reg2 = build_registry()
    with ServeCluster(reg2, replicas=1, max_batch=4, batch_wait_ms=1.0,
                      compile_cache=cache) as restarted:
        restarted.add_tenant("acme")
        base2 = restarted.warmup(img=IMG)
        assert cache.stats()["stores"] == stores_before  # nothing recompiled
        assert all(n == 0 for n in base2.values())       # restored, untraced
        for c, x in shots.items():
            restarted.submit_register("acme", c, x).result(60)
        t0 = time.perf_counter()
        res2 = restarted.submit_classify("acme", queries).result(60)
        first_ms = (time.perf_counter() - t0) * 1e3
        assert res2.class_ids == want_ids                # same model, bitwise
        np.testing.assert_array_equal(res2.sims, res.sims)
        assert restarted.trace_counts() == base2         # STILL zero traces
        assert first_ms < 5000                           # served, not compiled


def test_cluster_add_replica_warms_from_shared_artifacts(served, tmp_path):
    _, params = served
    reg = TenantRegistry()
    reg.register_backbone(
        "int", FSLPipeline(width=WIDTH, qcfg=QCFG).deploy(params, "int"),
        default=True)
    cache = CompileCache(str(tmp_path))
    rng = np.random.default_rng(21)
    with ServeCluster(reg, replicas=1, max_batch=2, batch_wait_ms=1.0,
                      compile_cache=cache) as cluster:
        cluster.add_tenant("t")
        base = cluster.warmup(img=IMG)
        t0 = time.perf_counter()
        cluster.add_replica()                            # shares warm artifacts
        assert time.perf_counter() - t0 < 2.0            # no recompile
        assert len(cluster.engines) == 2
        cluster.submit_register("t", "c", _frames(rng, 1)).result(60)
        for _ in range(4):                               # all via t's home
            r = cluster.submit_classify("t", _frames(rng, 1)).result(60)
            assert r.class_ids == ["c"]
        assert cluster.trace_counts() == base
        completed = sum(eng.metrics.snapshot()["completed"]
                        for eng in cluster.engines)
        assert completed == 5


def test_cluster_needs_at_least_one_replica():
    with pytest.raises(ValueError):
        ServeCluster(TenantRegistry(), replicas=0)


def test_cluster_tenant_home_affinity_and_quota_no_spill(served):
    """Tenants are pinned round-robin to home replicas, and a quota
    rejection is authoritative: it does NOT fail over to another replica
    (quota is policy; only queue-full capacity is routable)."""
    _, params = served
    reg = TenantRegistry()
    reg.register_backbone(
        "int", FSLPipeline(width=WIDTH, qcfg=QCFG).deploy(params, "int"),
        default=True)
    rng = np.random.default_rng(0)
    cluster = ServeCluster(reg, replicas=2, max_batch=4, max_queue=8,
                           tenant_quota=2, start=False)
    try:
        for t in ("a", "b"):
            cluster.add_tenant(t)
        assert [cluster.home_replica(t) for t in ("a", "b")] == [0, 1]
        with pytest.raises(KeyError):
            cluster.home_replica("nobody")
        # engines are stopped, so admitted work just sits in the queues:
        # each tenant can fill exactly its own home-replica quota ...
        futs = [cluster.submit_classify(t, _frames(rng, 1))
                for t in ("a", "b") for _ in range(2)]
        assert len(futs) == 4
        # ... and the over-quota submit is rejected as TenantOverQuota even
        # though the OTHER replica has both queue room and quota headroom
        # for this tenant — no spill.
        with pytest.raises(TenantOverQuota):
            cluster.submit_classify("a", _frames(rng, 1))
    finally:
        for eng in cluster.engines:
            eng.stop(drain=False)


# ---------------------------------------------------------------------------
# soak (slow): ISSUE 6 acceptance — 1000 multi-tenant requests, zero
# retraces after cache restore
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_soak_multitenant_zero_retrace_after_cache_restore(served, tmp_path):
    """Populate the compile cache, then 'restart' (fresh pipeline + registry)
    and push >= 1000 mixed register/classify requests from three tenants
    through two replicas: ZERO traces ever (warmup was pure restore), no
    rejections, per-tenant isolation, and every tenant's prototypes
    bit-for-bit equal to an offline NCM over that tenant's own shots."""
    _, params = served
    cache = CompileCache(str(tmp_path))

    def build_registry():
        reg = TenantRegistry()
        feats = FSLPipeline(width=WIDTH, qcfg=QCFG).deploy(params,
                                                           datapath="int")
        reg.register_backbone("int", feats, default=True)
        return reg

    # first boot: compile + publish, then throw the warm process away
    with ServeCluster(build_registry(), replicas=1, max_batch=16,
                      compile_cache=cache, start=False) as boot:
        boot.warmup(img=IMG)
    assert cache.stats()["stores"] > 0

    tenants = ("acme", "bob", "carol")
    rng = np.random.default_rng(42)
    n_req, n_classes = 1000, 4
    plan = []                                            # (tenant, kind, cls, x)
    for i in range(n_req):
        tenant = tenants[i % len(tenants)]
        if i < len(tenants) * n_classes or rng.random() < 0.15:
            c = i // len(tenants) % n_classes
            plan.append((tenant, "register", c,
                         _frames(rng, int(rng.integers(1, 5)))))
        else:
            plan.append((tenant, "classify", None,
                         _frames(rng, int(rng.integers(1, 4)))))

    reg = build_registry()
    with ServeCluster(reg, replicas=2, max_batch=16, max_queue=256,
                      batch_wait_ms=1.0, tenant_quota=0.5,
                      compile_cache=cache) as cluster:
        for t in tenants:
            cluster.add_tenant(t)
        base = cluster.warmup(img=IMG)
        assert all(n == 0 for n in base.values())        # restored, untraced
        futs, results = [], []
        for tenant, kind, c, x in plan:
            if kind == "register":
                futs.append(cluster.submit_register(tenant, c, x,
                                                    timeout=30.0))
            else:
                futs.append(cluster.submit_classify(tenant, x, timeout=30.0))
            # well-behaved clients bound their in-flight: a tenant's
            # capacity is its HOME replica's quota (128 here), not the
            # cluster-wide sum, so ~80/tenant stays safely under it
            if len(futs) >= 240:
                results.extend(f.result(timeout=120) for f in futs[:120])
                del futs[:120]
        results.extend(f.result(timeout=120) for f in futs)
        assert len(results) == n_req
        assert cluster.trace_counts() == base, "retraced under load"
        snap = cluster.metrics_snapshot()
        assert snap["completed"] == n_req
        assert snap["rejected"] == 0 and snap["over_quota"] == 0
        per_tenant = {t: sum(1 for p in plan if p[0] == t) for t in tenants}
        for t in tenants:
            assert snap["tenants"][t]["completed"] == per_tenant[t]
        stores = {t: reg.tenant_store(t) for t in tenants}

    feats = FSLPipeline(width=WIDTH, qcfg=QCFG).deploy(params, datapath="int")
    for t in tenants:
        by_class = {}
        for tenant, kind, c, x in plan:
            if tenant == t and kind == "register":
                by_class.setdefault(c, []).append(x)
        means, ids = stores[t].prototypes()
        assert set(ids) == set(by_class)
        for c, chunks in by_class.items():
            sup = np.concatenate([np.asarray(feats(jnp.asarray(ch)))
                                  for ch in chunks])
            offline = np.asarray(ncm.class_means(
                jnp.asarray(sup), jnp.zeros((len(sup),), jnp.int32), 1))[0]
            np.testing.assert_array_equal(means[ids.index(c)], offline)


@pytest.mark.slow
def test_soak_concurrent_tenants_quota_isolation(served):
    """Concurrent per-tenant submitter threads against tight quotas: the
    flooding tenant's rejections are ALL TenantOverQuota, the closed-loop
    victim (who keeps its own in-flight under quota, as a well-behaved
    client does) has none, and both sides' completed work is intact."""
    _, params = served
    reg = TenantRegistry()
    reg.register_backbone(
        "int", FSLPipeline(width=WIDTH, qcfg=QCFG).deploy(params, "int"),
        default=True)
    rng = np.random.default_rng(77)
    shots = _frames(rng, 2)
    with ServeCluster(reg, replicas=1, max_batch=8, max_queue=64,
                      batch_wait_ms=1.0, tenant_quota=4) as cluster:
        for t in ("noisy", "victim"):
            cluster.add_tenant(t)
            cluster.submit_register(t, "c", shots).result(60)
        cluster.warmup(img=IMG)
        stop = threading.Event()
        rejected = {"noisy": 0, "victim": 0}
        wrong_type = []

        def flood(tenant, n, pace_s, wait):
            # wait=True is a well-behaved closed-loop client (one request in
            # flight, never near its quota); wait=False fires blind and lets
            # admission control shed the excess
            for _ in range(n):
                if stop.is_set():
                    return
                try:
                    fut = cluster.submit_classify(tenant, _frames(rng, 1))
                    if wait:
                        fut.result(timeout=60)
                except TenantOverQuota:
                    rejected[tenant] += 1
                except ServeOverload as e:               # shared-queue spill
                    wrong_type.append(e)
                time.sleep(pace_s)

        noisy = threading.Thread(target=flood, args=("noisy", 400, 0.0, False))
        victim = threading.Thread(target=flood,
                                  args=("victim", 40, 0.01, True))
        noisy.start()
        victim.start()
        noisy.join(120)
        victim.join(120)
        stop.set()
        cluster.stop(drain=True)
        assert rejected["noisy"] > 0                     # quota actually bit
        assert rejected["victim"] == 0                   # victim unthrottled
        assert not wrong_type                            # never shared-queue
        snap = cluster.metrics_snapshot()
        assert snap["tenants"]["noisy"]["over_quota"] == rejected["noisy"]
        assert snap["tenants"]["victim"]["over_quota"] == 0
        assert snap["tenants"]["victim"]["completed"] >= 40
