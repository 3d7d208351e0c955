"""repro.explore.farm — the parallel, resumable DSE sweep farm (ISSUE 4
tentpole acceptance):

* a killed-and-restarted farm run completes the REMAINING points only
  (content-hash cache hits for everything already finished);
* ``publish_frontier`` leaves the registry serving a Pareto point whose
  served classifications are bit-for-bit equal to that point's sweep-time
  probe;
* content-addressed checkpoints (``CheckpointManager.save_named`` /
  ``content_key``) are atomic, GC-proof and identity-faithful.
"""

import hashlib

import numpy as np
import pytest

from repro.ckpt import CheckpointManager, content_key
from repro.explore import (
    DETERMINISTIC_KEYS,
    SweepFarm,
    probe_batch,
    publish_frontier,
    select_knee,
)
from repro.serve import ArtifactRegistry, PrototypeStore, ServeEngine

WIDTH, IMG, BENCH_BATCH = 4, 16, 2
GRID2 = [(3, 2), (6, 4)]

FARM_KW = dict(width=WIDTH, steps=2, episodes=2, n_base=6, n_novel=5,
               img=IMG, batch=8, bench_batch=BENCH_BATCH, bench_iters=1,
               verbose=False)


def _farm(cache_dir, **overrides) -> SweepFarm:
    return SweepFarm(str(cache_dir), **{**FARM_KW, **overrides})


@pytest.fixture(scope="module")
def farm_run(tmp_path_factory):
    """One shared 2-point farm run (the expensive part of this module)."""
    cache = tmp_path_factory.mktemp("farm_cache")
    farm = _farm(cache)
    return farm, farm.run(GRID2)


# ---------------------------------------------------------------------------
# resume: a killed farm restarts where it left off
# ---------------------------------------------------------------------------
def test_cold_run_computes_every_point(farm_run):
    _, result = farm_run
    assert result.cached == [False, False]
    assert result.computed == 2 and result.hits == 0
    assert len(result.points) == 2 and len(set(result.keys)) == 2
    for rec in result.points:
        assert rec["bitexact_int_vs_f32"]


def test_restarted_run_completes_remaining_points_only(farm_run):
    """The acceptance scenario: the first run 'died' after GRID2; a restart
    over a superset grid serves the finished points from cache (identical
    records) and computes exactly the new one."""
    farm, first = farm_run
    restarted = _farm(farm.cache_dir)        # fresh orchestrator, same cache
    result = restarted.run(GRID2 + [(4, 4)])
    assert result.cached == [True, True, False]
    assert result.computed == 1
    # cache hits return the records the first run computed, verbatim
    assert result.points[:2] == first.points
    assert result.keys[:2] == first.keys
    # and the whole thing is now cached: a re-run costs nothing
    again = _farm(farm.cache_dir).run(GRID2 + [(4, 4)])
    assert again.cached == [True, True, True]
    assert again.points == result.points


def test_cache_key_is_content_addressed(tmp_path):
    """Same config ⇒ same key (across farm instances); ANY identity field
    change ⇒ different key (a hit can never be a stale point); bench_iters
    is a timing budget, not identity."""
    a, b = _farm(tmp_path / "a"), _farm(tmp_path / "b")
    assert a.key_for(6, 4) == b.key_for(6, 4)
    assert a.key_for(6, 4) != a.key_for(4, 6)
    assert _farm(tmp_path / "c", steps=3).key_for(6, 4) != a.key_for(6, 4)
    assert _farm(tmp_path / "d", seed=1).key_for(6, 4) != a.key_for(6, 4)
    assert _farm(tmp_path / "e", bench_iters=9).key_for(6, 4) == \
        a.key_for(6, 4)


def test_thread_pool_dispatch_matches_serial(tmp_path):
    """workers>1 exercises the concurrent path (thread pool + device
    pinning); per-point streams are derived from (seed, W, A) alone, so the
    records' deterministic fields must equal the serial run's exactly."""
    tiny = dict(width=2, steps=1, episodes=1, n_base=4, n_novel=5, img=8,
                batch=4, bench_batch=2, bench_iters=1, verbose=False)
    grid = [(3, 2), (4, 4)]
    serial = SweepFarm(str(tmp_path / "s"), workers=1, **tiny).run(grid)
    threaded = SweepFarm(str(tmp_path / "t"), workers=2, **tiny).run(grid)
    assert threaded.cached == [False, False]
    for rs, rt in zip(serial.points, threaded.points):
        assert {k: rs[k] for k in DETERMINISTIC_KEYS} == \
            {k: rt[k] for k in DETERMINISTIC_KEYS}


# ---------------------------------------------------------------------------
# fault isolation (ISSUE 9): one raising candidate must not abort the farm
# ---------------------------------------------------------------------------
def test_failing_candidate_isolated_and_siblings_survive(tmp_path):
    """A grid with one raising candidate ((40, 4): unrepresentable spec)
    still returns results for every other candidate; the failure surfaces
    as a structured entry, not an exception, and is excluded from the
    frontier."""
    result = _farm(tmp_path / "c", workers=2).run([(3, 2), (40, 4), (4, 4)])
    assert result.errors[0] is None and result.errors[2] is None
    assert result.errors[1] and "ValueError" in result.errors[1]
    assert result.failed == [1]
    assert result.cached == [False, False, False]
    assert result.points[0]["bitexact_int_vs_f32"]
    assert result.points[2]["bitexact_int_vs_f32"]
    assert result.points[1]["error"] == result.errors[1]
    assert result.points[1]["label"] == "w40a4"
    assert 1 not in result.frontier and result.frontier
    # the JSON form carries the failure too
    assert result.to_dict()["errors"] == result.errors


def test_failed_point_resume_recomputes_only_the_failure(tmp_path,
                                                         monkeypatch):
    """ISSUE 9 acceptance: after a run where one candidate failed
    transiently, a re-run serves every finished sibling from cache and
    computes ONLY the failed candidate."""
    import importlib

    # the package re-exports the sweep() FUNCTION under the same name, so
    # resolve the submodule explicitly
    sweep_mod = importlib.import_module("repro.explore.sweep")
    real = sweep_mod.run_candidate

    def flaky(cand, **kw):
        if tuple(cand) == (6, 4):
            raise RuntimeError("transient trainer crash")
        return real(cand, **kw)

    farm = _farm(tmp_path / "c")
    monkeypatch.setattr(sweep_mod, "run_candidate", flaky)
    first = farm.run(GRID2)
    assert first.failed == [1] and "transient" in first.errors[1]
    assert first.errors[0] is None

    monkeypatch.setattr(sweep_mod, "run_candidate", real)
    second = _farm(tmp_path / "c").run(GRID2)
    assert second.cached == [True, False]      # only the failure recomputed
    assert second.failed == [] and second.errors == [None, None]
    assert second.points[0] == first.points[0]


def test_unknown_arch_fails_loudly_at_construction(tmp_path):
    with pytest.raises(KeyError, match="unknown recipe"):
        _farm(tmp_path / "c", arch="mystery-net")


def test_restore_point_arch_mismatch_raises(tmp_path):
    """A cache entry swept under one arch must refuse to restore as another
    (the pre-fix behaviour silently rebuilt resnet9-shaped params)."""
    from repro.core.recipes import register_recipe
    from repro.explore.farm import _restore_point

    farm = _farm(tmp_path / "c")
    result = farm.run([(3, 2)])
    assert result.failed == []
    register_recipe("other-net", ["verify_hw_mappable"],
                    description="test stub")
    with pytest.raises(ValueError, match="arch 'resnet9'"):
        _restore_point(str(tmp_path / "c"), result.keys[0], WIDTH,
                       BENCH_BATCH, arch="other-net")


@pytest.mark.slow
def test_process_pool_dispatch_matches_serial(tmp_path):
    """mode='process' (spawn context) must produce the same deterministic
    record fields as serial dispatch, isolate failures across the process
    boundary, and share the cache dir."""
    tiny = dict(width=2, steps=1, episodes=1, n_base=4, n_novel=5, img=8,
                batch=4, bench_batch=2, bench_iters=1, verbose=False)
    grid = [(3, 2), (40, 4), (4, 4)]
    serial = SweepFarm(str(tmp_path / "s"), workers=1, **tiny).run(grid)
    proc = SweepFarm(str(tmp_path / "p"), workers=2, mode="process",
                     **tiny).run(grid)
    assert proc.failed == [1] and "ValueError" in proc.errors[1]
    for rs, rp in zip([serial.points[i] for i in (0, 2)],
                      [proc.points[i] for i in (0, 2)]):
        assert {k: rs[k] for k in DETERMINISTIC_KEYS} == \
            {k: rp[k] for k in DETERMINISTIC_KEYS}
    # a thread-mode re-run over the process-populated cache is all hits
    again = SweepFarm(str(tmp_path / "p"), workers=1, **tiny).run(
        [grid[0], grid[2]])
    assert again.cached == [True, True]


def test_process_pool_refuses_on_accelerator(tmp_path, monkeypatch):
    """A chip serves one process at a time and this one already holds it:
    on an accelerator, mode='process' refuses before starting a child."""
    import types

    import jax

    tpu = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [tpu])
    farm = SweepFarm(str(tmp_path / "p"), workers=2, mode="process",
                     width=2, steps=1, verbose=False)
    with pytest.raises(RuntimeError, match="one process at a time"):
        farm.run([(3, 2), (4, 4)])
    assert not (tmp_path / "p").exists() or not any((tmp_path / "p").iterdir())


# ---------------------------------------------------------------------------
# publish: sweep → serve the knee, bit for bit
# ---------------------------------------------------------------------------
def test_publish_frontier_serves_the_knee_bit_for_bit(farm_run):
    """ISSUE 4 acceptance: after publish_frontier the registry default is a
    Pareto point, and classifications served through the engine are
    bit-for-bit what the point's sweep-time probe features imply."""
    farm, result = farm_run
    registry = ArtifactRegistry()
    names = publish_frontier(result, registry)
    assert names and len(registry) == len(result.frontier)

    # the default is the selected knee, with provenance metadata attached
    knee_idx = select_knee(result.points, result.frontier)
    rec = result.points[knee_idx]
    default = registry.get(None)
    assert default.name == f"w{rec['w_bits']}a{rec['a_bits']}-int"
    assert default.meta["knee"] and default.meta["cache_key"] == \
        result.keys[knee_idx]
    assert default.meta["weight_bytes"] == rec["weight_bytes_int"]

    # served features on the regenerated sweep-time probe == cached probe
    # features, bit for bit (digest included)
    cached = farm.restore_point(result.keys[knee_idx])
    probe = np.asarray(probe_batch(rec["point_seed"], BENCH_BATCH, IMG))
    served_feats = np.asarray(default.feats(probe))
    np.testing.assert_array_equal(served_feats, cached.probe_feats)
    assert hashlib.sha256(served_feats.tobytes()).hexdigest() == \
        rec["probe_digest"]

    # and end to end through the engine: register probe rows as two classes,
    # classify the probe — ids AND similarities must equal an offline NCM
    # over the sweep-time features exactly
    offline = PrototypeStore()
    offline.register("a", cached.probe_feats[:1])
    offline.register("b", cached.probe_feats[1:2])
    want_ids, want_sims = offline.classify(cached.probe_feats)

    with ServeEngine(registry, max_batch=4, batch_wait_ms=1.0) as eng:
        eng.warmup(img=IMG)
        eng.submit_register("a", probe[:1]).result(timeout=60)
        eng.submit_register("b", probe[1:2]).result(timeout=60)
        got = eng.submit_classify(probe).result(timeout=60)
    assert got.artifact == default.name
    assert got.class_ids == want_ids
    np.testing.assert_array_equal(got.sims, want_sims)


def test_publish_empty_farm_result_raises(farm_run):
    farm, result = farm_run
    import dataclasses

    empty = dataclasses.replace(result, points=[], frontier=[], keys=[],
                                cached=[], wall_s=[])
    with pytest.raises(ValueError, match="empty"):
        publish_frontier(empty, ArtifactRegistry())


def test_select_knee_prefers_smallest_within_tolerance():
    pts = [
        {"acc_mean": 0.90, "weight_bytes_int": 100},
        {"acc_mean": 0.89, "weight_bytes_int": 40},   # within tol, smaller
        {"acc_mean": 0.50, "weight_bytes_int": 10},   # frontier, too lossy
    ]
    assert select_knee(pts, [0, 1, 2], acc_tol=0.02) == 1
    assert select_knee(pts, [0, 1, 2], acc_tol=0.001) == 0
    with pytest.raises(ValueError):
        select_knee(pts, [])


# ---------------------------------------------------------------------------
# content-addressed checkpoints (the farm's resume substrate)
# ---------------------------------------------------------------------------
def test_content_key_is_canonical():
    assert content_key({"a": 1, "b": 2}) == content_key({"b": 2, "a": 1})
    assert content_key({"a": 1}) != content_key({"a": 2})
    assert len(content_key({"a": 1})) == 16
    assert content_key({"a": 1}, length=8) == content_key({"a": 1})[:8]


def test_named_checkpoint_roundtrip_and_meta(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    tree = {"w": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": np.ones((3,), np.float32)}
    assert not mgr.has_named("k1")
    mgr.save_named("k1", tree, meta={"acc": 0.5})
    assert mgr.has_named("k1") and mgr.all_named() == ["k1"]
    like = {"w": np.zeros((2, 3), np.float32), "b": np.zeros((3,), np.float32)}
    out = mgr.restore_named(like, "k1")
    np.testing.assert_array_equal(out["w"], tree["w"])
    np.testing.assert_array_equal(out["b"], tree["b"])
    assert mgr.named_meta("k1")["acc"] == 0.5
    with pytest.raises(FileNotFoundError):
        mgr.restore_named(like, "nope")


def test_named_checkpoints_survive_step_gc(tmp_path):
    """Named entries are a cache keyed by identity, not a history keyed by
    time — the keep-k GC on step checkpoints must never collect them."""
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mgr.save_named("cache-point", {"x": np.ones(2, np.float32)})
    for step in range(5):
        mgr.save(step, {"x": np.zeros(1, np.float32)})
    assert mgr.all_steps() == [3, 4]            # GC kept 2
    assert mgr.has_named("cache-point")         # cache untouched
    # and named entries never appear in the step listing
    assert mgr.latest_step() == 4


def test_named_checkpoint_concurrent_same_key_writers(tmp_path):
    """Two workers publishing the SAME key (duplicate grid points, or two
    farm processes sharing a cache dir) must each stage in a private tmp
    dir — whoever replaces last wins with a COMPLETE entry, never an
    interleaved/truncated one."""
    import threading

    mgr = CheckpointManager(str(tmp_path))
    payloads = [np.full((64, 64), i, np.float32) for i in range(8)]
    barrier = threading.Barrier(4)

    def writer(i):
        barrier.wait()
        for p in payloads:
            mgr.save_named("contested", {"x": p}, meta={"writer": i})

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    out = mgr.restore_named({"x": np.zeros((64, 64), np.float32)},
                            "contested")
    # the winning entry is one writer's LAST payload, intact
    np.testing.assert_array_equal(out["x"], payloads[-1])
    assert mgr.named_meta("contested")["writer"] in range(4)


def test_named_checkpoint_rejects_unsafe_names(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    for bad in ("../escape", "a/b", "", "sp ace"):
        with pytest.raises(ValueError, match="invalid checkpoint name"):
            mgr.save_named(bad, {"x": np.zeros(1)})
