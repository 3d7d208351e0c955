"""Parallel, resumable DSE sweep farm — sweep → select → deploy as ONE
automated system.

``repro.explore.sweep`` runs the paper's outer loop strictly serially,
re-pretrains every point from scratch on every invocation, and its output
dies in a JSON file.  This module closes the loop the related pipelines
(PEFSL's FPGA deployment flow, the MLPerf-Tiny codesign flow) treat as one
system:

* **Concurrent** — candidates dispatch over a thread pool, one worker per
  JAX device with per-point ``jax.default_device`` pinning, or (``mode=
  "process"``) over a spawn-context ``ProcessPoolExecutor`` for multi-process
  scaling beyond the GIL (each candidate is an independent train+compile+
  measure unit; on a single device the farm falls back to serial dispatch,
  same results by construction since every candidate owns its own PRNG
  stream via :func:`repro.explore.sweep.candidate_seed`).
* **Fault-isolated** — one raising candidate no longer aborts the farm: the
  failure is captured as a structured entry (``error=...``, ``cached=
  False``), every sibling still returns its result, and a re-run recomputes
  ONLY the failed candidates (the successes are cache hits).
* **Resumable** — each finished candidate (trained params + served-path
  probe features + the metrics record) is checkpointed atomically under a
  *content hash* of its full identity ``(arch, candidate, seed,
  train-config)`` (``ckpt.content_key`` / ``CheckpointManager.save_named``).
  A killed farm restarts where it left off; re-running with one new
  candidate costs one candidate; changing ANY config field changes the key
  and retrains — a cache hit is always the point you asked for.  Candidates
  are either uniform ``(W, A)`` tuples or per-layer
  :class:`~repro.core.quant.LayerQuantPlan` descriptors — both content-key
  the same way.
* **Publishing** — :func:`publish_frontier` compiles the Pareto-optimal
  points through ``FSLPipeline.deploy`` and registers them in a
  ``serve.ArtifactRegistry`` with provenance metadata (weight bytes,
  episode accuracy, latency, cache key, and — for mixed-precision points —
  the full per-layer plan), hot-swapping the registry default to the
  selected knee.  "Sweep → A/B-serve the knee" is one call; the sweep-time
  probe is regenerable from each record (``probe_batch``), so a published
  artifact can be audited bit-for-bit against the features it was swept
  with.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import time
from concurrent.futures import ProcessPoolExecutor, ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.ckpt.manager import CheckpointManager, content_key
from repro.core.recipes import recipe
from repro.data.synthetic import SyntheticImages
from repro.explore import sweep as _sweep
from repro.explore.sweep import (DEFAULT_GRID, Candidate, PointResult,
                                 as_candidate, candidate_config,
                                 candidate_content, candidate_label,
                                 pareto_frontier)
from repro.fsl.pipeline import FSLPipeline

__all__ = ["FarmResult", "SweepFarm", "publish_frontier", "select_knee"]

# Cache-layout version, hashed into every candidate's content key.  v2 =
# 63-bit candidate seeds + candidate descriptors (ISSUE 9): entries written
# under the 31-bit ``point_seed`` regime carry a DIFFERENT PRNG stream, so
# they must recompute rather than be silently replayed.
_CACHE_VERSION = 2


@dataclasses.dataclass
class FarmResult:
    """Outcome of one :meth:`SweepFarm.run` — records in grid order plus the
    cache/provenance bookkeeping the publish step needs.

    ``errors[i]`` is ``None`` for a completed candidate and the captured
    ``"ExcType: message"`` string for a failed one (whose ``points[i]`` is a
    structured failure stub, not a sweep record).  ``frontier`` only ranks
    completed candidates, but its indices still point into ``points``.
    """

    grid: List                      # candidate descriptors (canonical JSON)
    points: List[Dict]              # one sweep record (or failure stub) each
    frontier: List[int]             # Pareto indices into ``points``
    keys: List[str]                 # content-hash cache key per candidate
    cached: List[bool]              # True = served from cache, not computed
    wall_s: List[float]             # per-point wall-clock (≈0 for cache hits)
    cache_dir: str
    config: Dict                    # shared train config (arch, width, ...)
    errors: List[Optional[str]] = dataclasses.field(default_factory=list)

    @property
    def hits(self) -> int:
        return sum(self.cached)

    @property
    def failed(self) -> List[int]:
        return [i for i, e in enumerate(self.errors) if e is not None]

    @property
    def computed(self) -> int:
        return len(self.cached) - self.hits - len(self.failed)

    def to_dict(self) -> Dict:
        """JSON form — a strict superset of the serial ``sweep()`` dict."""
        return {
            "model": self.config.get("arch", "resnet9"),
            "backend": jax.default_backend(),
            "grid": list(self.grid), "points": self.points,
            "frontier": self.frontier, "keys": self.keys,
            "cached": self.cached, "wall_s": self.wall_s,
            "errors": self.errors,
            "cache_dir": self.cache_dir, "config": self.config,
        }

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=2, sort_keys=True)


def _point_task(cache_dir: str, cfg: Dict, bench_iters: int, cand_content,
                key: str, verbose: bool, data=None
                ) -> Tuple[Dict, str, bool, float, Optional[str]]:
    """ONE candidate: cache check → run → atomic publish.

    Module-level and driven purely by picklable arguments so thread,
    serial, and spawn-context process dispatch all share it (a process
    child regenerates ``SyntheticImages`` from the config).  A raising
    candidate returns a structured failure entry instead of propagating —
    the farm's fault-isolation contract.  ``run_candidate`` is resolved
    through the module attribute at call time (monkeypatch-friendly).
    """
    cand = as_candidate(cand_content)
    label = candidate_label(cand)
    mgr = CheckpointManager(cache_dir)
    t0 = time.perf_counter()
    if mgr.has_named(key):
        record = mgr.named_meta(key)["record"]
        if verbose:
            print(f"farm,{label},cache_hit,{key}")
        return record, key, True, time.perf_counter() - t0, None
    if data is None:
        data = SyntheticImages(n_base=cfg["n_base"], n_novel=cfg["n_novel"],
                               seed=cfg["seed"], img=cfg["img"])
    try:
        pr = _sweep.run_candidate(
            cand, width=cfg["width"], steps=cfg["steps"],
            episodes=cfg["episodes"], batch=cfg["batch"],
            bench_batch=cfg["bench_batch"], bench_iters=bench_iters,
            seed=cfg["seed"], data=data, arch=cfg["arch"], verbose=verbose)
    except Exception as e:  # noqa: BLE001 — isolate ANY per-point failure
        wall = time.perf_counter() - t0
        err = f"{type(e).__name__}: {e}"
        if verbose:
            print(f"farm,{label},failed,{err}")
        stub = {"label": label, "candidate": candidate_content(cand),
                "error": err}
        return stub, key, False, wall, err
    wall = time.perf_counter() - t0
    # atomic publish AFTER the point fully finished: a kill mid-point
    # leaves no entry, so resume recomputes it — never a half-result
    mgr.save_named(
        key, {"params": pr.params, "probe_feats": pr.probe_feats},
        meta={"record": pr.record, "config": cfg, "wall_s": wall})
    return pr.record, key, False, wall, None


class SweepFarm:
    """Concurrent, resumable orchestrator over ``run_candidate``.

    The constructor pins the full train config (including ``arch``,
    validated against the BuildRecipe registry up front); :meth:`key_for`
    hashes it together with a candidate into the cache identity.
    ``workers=None`` means one worker per JAX device (serial on a single
    device); any explicit count is honored — every candidate's PRNG stream
    is derived from ``(seed, candidate)`` alone, so results are
    scheduling-independent.  ``mode="process"`` dispatches over a
    spawn-context process pool instead of threads (each child re-imports
    JAX; the shared cache directory is the only coordination point).  It
    runs on the CPU backend only: on an accelerator every child would need
    the chip this process holds, so it refuses.
    """

    def __init__(self, cache_dir: str, *, width: int = 8, steps: int = 120,
                 episodes: int = 10, n_base: int = 12, n_novel: int = 6,
                 img: int = 32, batch: int = 32, bench_batch: int = 8,
                 bench_iters: int = 10, seed: int = 0,
                 workers: Optional[int] = None, mode: str = "thread",
                 arch: str = "resnet9", verbose: bool = True):
        if mode not in ("thread", "process"):
            raise ValueError(f"mode must be 'thread' or 'process', got {mode!r}")
        recipe(arch).workload_hooks("fsl")  # fail loudly BEFORE any training
        self.cache_dir = cache_dir
        self.mgr = CheckpointManager(cache_dir)
        self.config = {
            "arch": str(arch), "width": int(width), "steps": int(steps),
            "episodes": int(episodes), "n_base": int(n_base),
            "n_novel": int(n_novel), "img": int(img), "batch": int(batch),
            "bench_batch": int(bench_batch), "seed": int(seed),
        }
        self.bench_iters = int(bench_iters)   # timing budget: not identity
        self.workers = workers
        self.mode = mode
        self.verbose = verbose

    # -- cache identity -----------------------------------------------------
    def key_for(self, cand, a_bits: Optional[int] = None) -> str:
        """Content hash of (train-config, cache version, candidate) — the
        candidate's cache key.  Accepts any candidate descriptor, or the
        historical ``key_for(W, A)`` two-argument form.

        ``bench_iters`` is deliberately excluded: it only changes how long
        the latency measurement averages, not what the point IS; everything
        else (arch, seed, steps, width, data sizes) is identity.  The
        ``cache_v`` field versions the layout: bumping it (v2 = 63-bit
        seeds, candidate descriptors) orphans stale entries instead of
        silently replaying results computed under a different PRNG stream.
        """
        if a_bits is not None:
            cand = (cand, a_bits)
        return content_key({**self.config, "cache_v": _CACHE_VERSION,
                            "candidate": candidate_content(cand)})

    # -- run ----------------------------------------------------------------
    def run(self, grid: Sequence[Candidate] = DEFAULT_GRID) -> FarmResult:
        grid = [as_candidate(c) for c in grid]
        cfg = self.config
        contents = [candidate_content(c) for c in grid]
        keys = [self.key_for(c) for c in grid]
        devices = jax.devices()
        workers = self.workers if self.workers is not None else len(devices)
        workers = max(min(workers, len(grid)), 1)

        if self.mode == "process" and workers > 1:
            import multiprocessing as mp

            from repro.runtime import refuse_child_processes_on_accelerator

            refuse_child_processes_on_accelerator("SweepFarm(mode='process')")

            ctx = mp.get_context("spawn")   # no forked JAX runtime state
            with ProcessPoolExecutor(max_workers=workers,
                                     mp_context=ctx) as ex:
                futs = [ex.submit(_point_task, self.cache_dir, cfg,
                                  self.bench_iters, contents[i], keys[i],
                                  self.verbose)
                        for i in range(len(grid))]
                outs = [f.result() for f in futs]
        else:
            data = SyntheticImages(n_base=cfg["n_base"],
                                   n_novel=cfg["n_novel"],
                                   seed=cfg["seed"], img=cfg["img"])

            def one(i: int):
                dev = devices[i % len(devices)]
                pin = (jax.default_device(dev) if len(devices) > 1
                       else contextlib.nullcontext())
                with pin:
                    return _point_task(self.cache_dir, cfg, self.bench_iters,
                                       contents[i], keys[i], self.verbose,
                                       data=data)

            if workers <= 1:
                outs = [one(i) for i in range(len(grid))]
            else:
                with ThreadPoolExecutor(max_workers=workers,
                                        thread_name_prefix="sweep-farm") as ex:
                    outs = list(ex.map(one, range(len(grid))))

        points = [o[0] for o in outs]
        errors = [o[4] for o in outs]
        ok = [i for i, e in enumerate(errors) if e is None]
        frontier = [ok[j] for j in pareto_frontier([points[i] for i in ok])]
        result = FarmResult(
            grid=contents, points=points, frontier=frontier,
            keys=[o[1] for o in outs], cached=[o[2] for o in outs],
            wall_s=[o[3] for o in outs], cache_dir=self.cache_dir,
            config=dict(cfg), errors=errors)
        if self.verbose:
            print(f"farm,done,{result.computed} computed,"
                  f"{result.hits} cache hits,{len(result.failed)} failed,"
                  f"frontier={result.frontier}")
        return result

    # -- cache access -------------------------------------------------------
    def restore_point(self, key: str) -> PointResult:
        return _restore_point(self.cache_dir, key, self.config["width"],
                              self.config["bench_batch"],
                              arch=self.config["arch"])


def _restore_point(cache_dir: str, key: str, width: int, bench_batch: int,
                   arch: str = "resnet9") -> PointResult:
    """Load a cached point (params + probe features + record) by key.

    The restore skeleton comes from the BuildRecipe registry's FSL hooks —
    never a hard-coded backbone — and the entry's recorded arch is checked
    against the requested one FIRST: a mismatch raises instead of silently
    restoring wrong-shaped params into the wrong architecture.
    """
    mgr = CheckpointManager(cache_dir)
    meta = mgr.named_meta(key)
    stored = ((meta.get("record") or {}).get("arch")
              or (meta.get("config") or {}).get("arch"))
    if stored is not None and stored != arch:
        raise ValueError(
            f"cache entry {key} was swept with arch '{stored}' but the "
            f"restore requested '{arch}' — refusing a wrong-shaped restore")
    hooks = recipe(arch).workload_hooks("fsl")
    like = {
        "params": hooks.init_params(jax.random.PRNGKey(0), width),
        "probe_feats": np.zeros((bench_batch, hooks.feature_dim(width)),
                                np.float32),
    }
    tree = mgr.restore_named(like, key)
    return PointResult(record=meta["record"],
                       params=tree["params"],
                       probe_feats=np.asarray(tree["probe_feats"]))


def select_knee(points: Sequence[Dict], frontier: Sequence[int],
                acc_tol: float = 0.02) -> int:
    """The frontier point to serve by default: smallest int weight footprint
    within ``acc_tol`` of the frontier's best accuracy — the paper's knee
    argument (w6a4 matches w8a8 accuracy at a fraction of the storage)
    expressed as a rule instead of a human reading Table II."""
    if not frontier:
        raise ValueError("empty frontier: nothing to select a knee from")
    best = max(points[i]["acc_mean"] for i in frontier)
    good = [i for i in frontier if points[i]["acc_mean"] >= best - acc_tol]
    return min(good, key=lambda i: (points[i]["weight_bytes_int"],
                                    -points[i]["acc_mean"]))


def publish_frontier(result: FarmResult, registry, *, datapath: str = "int",
                     set_default: bool = True, acc_tol: float = 0.02
                     ) -> List[str]:
    """Compile the Pareto-optimal points and register them for serving.

    For every frontier index: restore the cached params, deploy through an
    ``FSLPipeline`` on EXACTLY the grid the candidate was swept on (uniform
    or per-layer — ``candidate_config`` is the shared convention), and
    register ``"{label}-{datapath}"`` (``w6a4-int``, ``mp-<digest>-int``) in
    ``registry`` with provenance metadata (weight bytes, episode accuracy,
    latency, cache key, probe digest, and the full per-layer plan for
    mixed-precision points).  The registry default hot-swaps to the
    :func:`select_knee` point, so the next anonymous request is served by
    the knee — "sweep → A/B-serve the knee" as one call.

    Returns the registered artifact names in frontier order.
    """
    if not result.points:
        raise ValueError("cannot publish an empty farm result")
    knee = select_knee(result.points, result.frontier, acc_tol)
    arch = result.config.get("arch", "resnet9")
    names: List[str] = []
    for i in result.frontier:
        rec = result.points[i]
        cand = as_candidate(rec.get("candidate",
                                    (rec["w_bits"], rec["a_bits"])))
        pr = _restore_point(result.cache_dir, result.keys[i],
                            result.config["width"],
                            result.config["bench_batch"], arch=arch)
        pipe = FSLPipeline(width=result.config["width"],
                           qcfg=candidate_config(cand), arch=arch)
        feats = pipe.deploy(pr.params, datapath=datapath)
        name = f"{rec.get('label', candidate_label(cand))}-{datapath}"
        # provenance must describe the datapath actually deployed — an f32
        # publication must not carry the int artifact's (~4x smaller)
        # footprint or its latency
        dp = "int" if datapath == "int" else "f32"
        registry.register(
            name, feats,
            default=(set_default and i == knee),
            meta={
                "arch": arch, "label": rec.get("label"),
                "candidate": rec.get("candidate"),
                "plan": rec.get("plan"),
                "w_bits": rec["w_bits"], "a_bits": rec["a_bits"],
                "datapath": datapath,
                "weight_bytes": rec[f"weight_bytes_{dp}"],
                "acc_mean": rec["acc_mean"], "acc_ci95": rec["acc_ci95"],
                "ms_per_batch": rec[f"{dp}_ms_per_batch"],
                "point_seed": rec["point_seed"],
                "probe_digest": rec["probe_digest"],
                # modeled per-node cost attribution (repro.obs.costmodel):
                # estimated hardware latency + the dominant node, carried
                # into serving provenance so a served artifact explains its
                # own cost profile (absent on records from pre-obs sweeps)
                "modeled_ms": rec.get("modeled_ms"),
                "cost_top": rec.get("cost_top"),
                "cache_key": result.keys[i], "knee": i == knee,
            })
        names.append(name)
    return names


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cache-dir", default="FARM_cache")
    ap.add_argument("--quick", action="store_true",
                    help="tiny budget: fewer steps/episodes (CI smoke)")
    ap.add_argument("--out", default="FARM_frontier.json")
    ap.add_argument("--width", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workers", type=int, default=None)
    ap.add_argument("--mode", choices=["thread", "process"], default="thread")
    args = ap.parse_args(argv)
    kw = dict(width=args.width, seed=args.seed, workers=args.workers,
              mode=args.mode)
    if args.quick:
        kw.update(width=min(args.width, 8), steps=20, episodes=3,
                  bench_iters=3)
    farm = SweepFarm(args.cache_dir, **kw)
    result = farm.run()
    result.write(args.out)
    print(f"farm,written,{args.out}")


if __name__ == "__main__":
    main()
