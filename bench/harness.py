"""The benchmark harness: resolve a cell by name, set the system up, drive
its traffic for a window, check every answer against the plain reference,
and reduce what was recorded to the cell's metrics.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by name:

* ``bench/configs/<config>.json`` -- sizes, engine settings, limits; its
  ``model`` names both the program's build recipe that is served and the
  plain reference ``bench/configs/<model>.py``, which also counts the
  model's work (``weight_matmuls``, ``macs_per_frame``);
* ``bench/traffic/<mix>.json`` -- parameters of :mod:`traffic`;
* ``bench/layers/<metric>.py`` -- a ``read(run)`` that returns the metric,
  or ``None`` where it finds nothing to read.

A cell ``<config>.<mix>`` is one entry of ``workloads`` in
``BENCHMARK.json``; the metrics it reports are the entries of
``end_to_end`` and ``per_layer`` that list it (or list no cells).
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import devtrace
import traffic as trafficmod
from peaks import peaks

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
DRAIN_S = 60.0              # how long answers due in the window may lag
KEEP_BATCHES = 32           # served batches whose features are compared


# ---------------------------------------------------------------------------
# cells, found by name
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    config: str
    root: str                   # the checkout its files were found in


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _lists(entry: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def resolve(bench: Dict[str, Any], name: str, root: str = ROOT) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    return make_cell(bench, w["config"], w["traffic"], int(w["chips"]), root)


def make_cell(bench: Dict[str, Any], config: str, traffic: str,
              chips: int = 1, root: str = ROOT) -> Cell:
    """The cell ``<config>.<traffic>`` from its files, with the metrics of
    ``bench`` that list it (none where it is not one of its workloads)."""
    name = f"{config}.{traffic}"
    entries = {c["name"]: c for c in bench["configs"]}
    path = (entries[config]["file"] if config in entries
            else os.path.join("bench", "configs", f"{config}.json"))
    with open(os.path.join(root, path)) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "bench", "traffic", f"{traffic}.json")) as f:
        mix = json.load(f)
    listed = any(w["name"] == name for w in bench["workloads"])
    e2e = [m for m in bench["end_to_end"] if listed and _lists(m, name)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if m["moves"] in reported and _lists(m, name)]
    return Cell(name, chips, cfg, mix, e2e, layer, config, root)


def load_file(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_module(cfg: Dict[str, Any], root: str = ROOT):
    """The configuration's plain reference, ``bench/configs/<model>.py``."""
    return load_file(os.path.join(root, "bench", "configs",
                                  f"{cfg['model']}.py"),
                     f"bench_model_{cfg['model']}")


def reader(metric: str, root: str = ROOT) -> Callable:
    return load_file(os.path.join(root, "bench", "layers", f"{metric}.py"),
                     f"bench_layer_{metric.replace('.', '_')}").read


# ---------------------------------------------------------------------------
# what the window lets in: compiles, collector pauses
# ---------------------------------------------------------------------------
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


class Watch:
    """Counts backend compiles, jaxpr traces and garbage-collector pauses
    while :attr:`active`."""

    _installed: List["Watch"] = []

    def __init__(self):
        self.active = False
        self.compiles = self.traces = self.gc_pauses = 0
        self.compile_s = self.gc_max_s = 0.0
        self._gc_t0 = None
        if not Watch._installed:
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(
                Watch._on_duration)
            gc.callbacks.append(Watch._on_gc)
        Watch._installed[:] = [self]

    @staticmethod
    def _on_duration(event: str, duration: float, **_) -> None:
        w = Watch._installed[0]
        if not w.active:
            return
        if event == COMPILE_EVENT:
            w.compiles += 1
            w.compile_s += duration
        elif event == TRACE_EVENT:
            w.traces += 1

    @staticmethod
    def _on_gc(phase: str, info: Dict) -> None:
        w = Watch._installed[0]
        if phase == "start":
            w._gc_t0 = time.perf_counter()
        elif w._gc_t0 is not None:
            if w.active:
                w.gc_pauses += 1
                w.gc_max_s = max(w.gc_max_s, time.perf_counter() - w._gc_t0)
            w._gc_t0 = None


# ---------------------------------------------------------------------------
# the system under test: few-shot serving of one deployed backbone
# ---------------------------------------------------------------------------
class SpanList:
    """Exporter for the program's tracer: keeps every span in memory."""

    def __init__(self):
        self.events: List[Dict] = []

    def export(self, event: Dict) -> None:
        self.events.append(event)


class Recorder:
    """The deployed feature function, keeping a seeded sample of the
    batches it served while :attr:`on` (reservoir of ``keep``) and the
    count of calls per padded batch size (its bucket)."""

    def __init__(self, feats, keep: int, seed: int):
        self._feats = feats
        self.deployed_model = feats.deployed_model
        self.keep = keep
        self.rng = np.random.default_rng([int(seed), 11])
        self.on = False
        self.seen = 0
        self.sizes: Dict[int, int] = {}
        self.kept: List[tuple] = []

    def trace_count(self) -> int:
        return self._feats.trace_count()

    def warmup(self, buckets, img: int = 32, cache=None, metrics=None,
               label: Optional[str] = None):
        """One program per bucket, compiled side by side in threads (the
        compiler runs without the interpreter lock)."""
        def one(b):
            self._feats.warmup([b], img=img, cache=cache, metrics=metrics,
                               label=label)
        with ThreadPoolExecutor(len(buckets)) as ex:
            list(ex.map(one, buckets))
        return self._feats.warmup(buckets, img=img, cache=cache,
                                  metrics=metrics, label=label)

    def __call__(self, x):
        out = self._feats(x)
        if self.on:
            self.seen += 1
            n = int(np.shape(x)[0])
            self.sizes[n] = self.sizes.get(n, 0) + 1
            if len(self.kept) < self.keep:
                self.kept.append((x, out))
            else:
                j = int(self.rng.integers(self.seen))
                if j < self.keep:
                    self.kept[j] = (x, out)
        return out


def quant_config(cfg: Dict[str, Any]):
    from repro.core.quant import FixedPointSpec, QuantConfig

    q = cfg["quant"]
    return QuantConfig(weight=FixedPointSpec(**q["weight"]),
                       act=FixedPointSpec(**q["act"]))


def check_backbone(cell: Cell) -> None:
    """Raise unless the program registers the build recipe that the cell's
    configuration names in ``model``."""
    from repro.core.recipes import list_recipes, recipe

    try:
        recipe(cell.cfg["model"])
    except KeyError:
        raise ValueError(
            f"configuration {cell.config!r} names model "
            f"{cell.cfg['model']!r}, which the program registers no build "
            f"recipe for; registered: {sorted(list_recipes())}") from None


class FSLServe:
    """ServeEngine over one ``FSLPipeline.deploy`` artifact of the build
    recipe the configuration's ``model`` names, with the configuration's
    support set registered and every shape warmed."""

    ARTIFACT = "served"

    def __init__(self, cfg: Dict[str, Any], params, seed: int, tracer):
        from repro.fsl.pipeline import FSLPipeline
        from repro.serve import ArtifactRegistry, ServeEngine

        pipe = FSLPipeline(arch=cfg["model"], width=int(cfg["width"]),
                           qcfg=quant_config(cfg),
                           easy_augment=bool(cfg["easy_augment"]))
        self.recorder = Recorder(pipe.deploy(params, datapath=cfg["datapath"]),
                                 KEEP_BATCHES, seed)
        self.registry = ArtifactRegistry()
        self.registry.register(self.ARTIFACT, self.recorder, default=True)
        e = cfg["engine"]
        self.engine = ServeEngine(
            self.registry, max_batch=int(e["max_batch"]),
            max_queue=int(e["max_queue"]), batch_wait_ms=float(e["batch_wait_ms"]),
            buckets=tuple(e["buckets"]), tracer=tracer)
        self.img = int(cfg["img"])

    def compile(self) -> None:
        """Every bucket's backbone program."""
        import jax

        # The weights are constants of the backbone programs, and each seed
        # makes new ones: no later run can use these programs, so they are
        # compiled in every run and not written to the persistent cache.
        keep = jax.config.jax_persistent_cache_min_compile_time_secs
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)
        try:
            self.engine.warmup(img=self.img)
        finally:
            jax.config.update("jax_persistent_cache_min_compile_time_secs", keep)

    def register(self, support_x: np.ndarray, support_y: np.ndarray) -> None:
        for way in sorted(set(int(y) for y in support_y)):
            self.engine.submit_register(way, support_x[support_y == way]
                                        ).result(120)
        # the head's programs at this class count, for every query bucket
        self.engine.warmup(img=self.img)

    def warm_path(self, frames: np.ndarray) -> None:
        """One classify of each bucket's size through the whole engine."""
        for b in self.engine.buckets:
            self.engine.submit_classify(frames[:b]).result(120)

    def submit(self, x):
        return self.engine.submit_classify(x)

    def stop(self) -> None:
        self.engine.stop()


# ---------------------------------------------------------------------------
# the comparison that decides ``correct``
# ---------------------------------------------------------------------------
def compare(batches, answers, ref_feats: Callable, ref_sims: np.ndarray,
            n_way: int, id_margin: float, sim_limit: float):
    """Readings of one run against the reference.

    ``batches``: ``(x, features)`` served by the backbone; ``answers``:
    ``(frame indices, sims (k, C), class ids)`` per answered request;
    ``ref_feats(x)``: reference features; ``ref_sims``: reference
    similarities of every pool frame.

    Returns the readings and, per answer, whether it is right: its sims
    within ``sim_limit`` of the reference, and its class the reference's
    wherever the reference's best class leads the next by ``id_margin``.
    """
    feat_gap = 0.0
    for x, f in batches:
        r = ref_feats(x)
        f = np.asarray(f, np.float64)
        scale = max(float(np.abs(r).max()), 1e-30)
        if f.shape != r.shape or not np.isfinite(f).all():
            feat_gap = float("inf")
            continue
        feat_gap = max(feat_gap, float(np.abs(f - r).max()) / scale)
    top2 = np.sort(ref_sims, axis=1)[:, -2:]
    clear = (top2[:, 1] - top2[:, 0]) > id_margin
    best = ref_sims.argmax(axis=1)
    sim_gap, wrong_ids, right = 0.0, 0, []
    for frames, s, ids in answers:
        s = np.asarray(s, np.float64)
        ok_shape = (s.shape == (len(frames), n_way) and np.isfinite(s).all()
                    and len(ids) == len(frames))
        gap = (float(np.abs(s - ref_sims[frames]).max()) if ok_shape
               else float("inf"))
        sim_gap = max(sim_gap, gap)
        bad = sum(1 for f, c in zip(frames, ids)
                  if clear[f] and c != int(best[f])) if ok_shape else len(frames)
        wrong_ids += bad
        right.append(ok_shape and bad == 0 and gap <= sim_limit)
    return {"feat_gap": feat_gap, "sim_gap": sim_gap,
            "wrong_ids": wrong_ids}, np.asarray(right, bool)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Outcome:
    result: Dict[str, Any]
    notes: List[str]            # earlier lines of standard output
    checks: List[str]           # last lines of standard error
    evidence: Dict[str, Any]    # what a control needs to stand in its place


def device_info(chips: int) -> Dict[str, Any]:
    import jax

    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class Session:
    """One cell set up in this process: weights and frames from the seed,
    the system deployed, its support set registered and every shape
    warmed.  :meth:`window` drives traffic through it."""

    def __init__(self, cell: Cell, seed: int,
                 fault: Optional[Callable] = None):
        import jax

        from repro.data.synthetic import SyntheticImages
        from repro.obs import Tracer

        self.cell, self.seed = cell, seed
        cfg, mix = cell.cfg, cell.traffic
        self.phases: Dict[str, float] = {}       # set-up seconds by phase
        t = time.perf_counter()
        check_backbone(cell)
        self.model = model_module(cfg, cell.root)
        self.watch = Watch()
        self.spans = SpanList()
        self.tracer = Tracer(exporter=self.spans, enabled=False)
        self.params = jax.block_until_ready(self.model.make_params(seed, cfg))
        t = self._phase("weights", t)
        n_way, n_pool = int(cfg["n_way"]), int(mix["pool_frames"])
        data = SyntheticImages(n_base=0, n_novel=n_way, seed=seed,
                               img=int(cfg["img"]))
        self.ep = data.episode(np.random.default_rng([int(seed), 3]),
                               n_way=n_way, k_shot=int(cfg["k_shot"]),
                               n_query=-(-n_pool // n_way))
        order = np.random.default_rng([int(seed), 5]).permutation(
            len(self.ep["query_x"]))[:n_pool]
        self.pool = np.ascontiguousarray(self.ep["query_x"][order][:, None])
        t = self._phase("frames", t)
        self.system = FSLServe(cfg, self.params, seed, self.tracer)
        t = self._phase("deploy", t)
        self.system.compile()
        t = self._phase("compile", t)
        self.system.register(self.ep["support_x"], self.ep["support_y"])
        t = self._phase("support set and head", t)
        self.system.warm_path(self.pool[:, 0])
        self._phase("one call per bucket", t)
        if fault is not None:
            fault(self.system)

    def _phase(self, name: str, t: float) -> float:
        now = time.perf_counter()
        self.phases[name] = now - t
        return now

    def window(self, mix: Dict[str, Any], seconds: float, trace: bool,
               t_start: Optional[float] = None) -> Dict[str, Any]:
        """Run ``seconds`` of ``mix`` and wait for its answers.  Returns the
        request log and the window's clock readings (``setup_s`` from
        ``t_start`` to the first request due)."""
        import jax

        from repro.serve import ServeOverload

        gen = trafficmod.Generator(mix, self.pool, self.seed,
                                   self.system.submit, refused=ServeOverload)
        w = self.watch
        w.compiles = w.traces = w.gc_pauses = 0
        w.compile_s = w.gc_max_s = 0.0
        self.spans.events.clear()
        prof_dir = None
        if trace:
            prof_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # it would slow the host path
            jax.profiler.start_trace(prof_dir, profiler_options=opts)
        gc.collect()
        gc.freeze()
        with jax.profiler.TraceAnnotation(devtrace.SYNC):
            t_sync = time.perf_counter()
        self.tracer.configure(enabled=trace)
        self.system.recorder.on = True
        self.system.recorder.sizes.clear()
        w.active = True
        t0 = time.perf_counter()
        log = gen.run(t0, seconds)
        t_end = t0 + seconds
        log.settle(max(t_end, time.perf_counter()) + DRAIN_S)
        t_settled = time.perf_counter()
        w.active = False
        self.system.recorder.on = False
        self.tracer.configure(enabled=False)
        gc.unfreeze()
        if trace:
            jax.profiler.stop_trace()
        return {"log": log, "t0": t0, "t_end": t_end, "t_settled": t_settled,
                "t_sync": t_sync, "prof_dir": prof_dir,
                "sizes": dict(sorted(self.system.recorder.sizes.items())),
                "setup_s": None if t_start is None else t0 - t_start}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, fault: Optional[Callable] = None,
             keep_trace: Optional[str] = None) -> Outcome:
    """Set up, run one window of ``seconds``, check, and reduce.

    ``t_start`` is the ``perf_counter`` reading at process start.
    ``fault``, for the harness's own tests, gets the built system before
    the window and may break it.  ``keep_trace`` names a directory that
    receives the traced window's profile and spans instead of deleting
    them."""
    cfg, mix = cell.cfg, cell.traffic
    sess = Session(cell, seed, fault)
    win = sess.window(mix, seconds, trace, t_start)
    log, t0, t_end = win["log"], win["t0"], win["t_end"]
    t_settled, setup_s, prof_dir = win["t_settled"], win["setup_s"], win["prof_dir"]
    watch, spans, system = sess.watch, sess.spans, sess.system
    model, params, ep, pool = sess.model, sess.params, sess.ep, sess.pool
    phases = sess.phases
    n_way = int(cfg["n_way"])
    device = device_info(cell.chips)

    # -- the answers, then the program's state is freed ---------------------
    answered = [i for i, s in enumerate(log.status) if s == trafficmod.OK]
    answers = [(np.asarray([log.frames[i]]), np.asarray(log.sims[i]),
                list(log.ids[i])) for i in answered]
    batches = [(np.asarray(x), np.asarray(f)) for x, f in
               system.recorder.kept]
    system.stop()
    del system, sess
    gc.collect()

    # -- the reference --------------------------------------------------------
    ref_support = model.features(params, ep["support_x"], cfg)
    protos = model.prototypes(ref_support, ep["support_y"], n_way)
    ref_sims = model.sims(model.features(params, pool[:, 0], cfg), protos)
    limits = cfg["limits"]
    readings, right = compare(
        batches, answers, lambda x: model.features(params, x, cfg),
        ref_sims, n_way, float(cfg["id_margin"]), float(limits["sim_gap"]))
    correct = all(readings[k] <= limits[k] for k in limits)

    # -- per-request fate -----------------------------------------------------
    n = len(log)
    good = np.zeros(n, bool)
    good[np.asarray(answered, int)] = right
    done = np.asarray([d if d is not None else t_settled for d in log.done])
    failed = int(n - good.sum())
    fate = {s: log.status.count(s) for s in
            (trafficmod.REFUSED, trafficmod.ERROR, trafficmod.UNANSWERED)}
    fate["wrong"] = int(len(answered) - right.sum())

    in_window = good & (done <= t_end)
    e2e_values = {
        "setup_s": setup_s,
        "frames_per_s": float(np.sum(in_window) / seconds),
    }
    per_s = np.bincount(np.minimum((done[in_window] - t0).astype(int),
                                   int(np.ceil(seconds)) - 1),
                        minlength=int(np.ceil(seconds)))
    notes = [
        f"device: {device['platform']} {device['kind']} x{device['count']}",
        f"set-up: {setup_s:.3f} s to the first request due; "
        + ", ".join(f"{k} {v:.3f} s" for k, v in phases.items()),
        f"window: {seconds} s, {n} requests, {int(good.sum())} right, "
        f"refused {fate['refused']}, errors {fate['error']}, unanswered "
        f"{fate['unanswered']}, wrong {fate['wrong']}; settled "
        f"{t_settled - t_end:.3f} s after the close",
        f"inside the window: compiles {watch.compiles} "
        f"({watch.compile_s:.3f} s), jaxpr traces {watch.traces}, "
        f"gc pauses {watch.gc_pauses} (longest {watch.gc_max_s * 1e3:.3f} "
        f"ms), generator lateness max {log.lateness_s * 1e3:.3f} ms",
        f"backbone calls by padded batch size: {win['sizes']}; frames "
        f"answered per second of the window: min {int(per_s.min())}, max "
        f"{int(per_s.max())}",
    ]

    result: Dict[str, Any] = {"correct": bool(correct), "attempted": n,
                              "failed": failed}
    if not trace:
        metrics = {m["name"]: {"value": e2e_values[m["name"]],
                               "unit": m["unit"]} for m in cell.end_to_end}
    else:
        xplane = devtrace.find_xplane(prof_dir)
        dt = devtrace.DeviceTrace.from_file(xplane, win["t_sync"])
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(xplane, os.path.join(keep_trace, "window.xplane.pb"))
            with open(os.path.join(keep_trace, "window.json"), "w") as f:
                json.dump({"t_sync": win["t_sync"], "t0": t0, "t_end": t_end,
                           "kind": device["kind"], "cell": cell.name,
                           "spans": spans.events}, f)
        shutil.rmtree(prof_dir, ignore_errors=True)
        run = TraceRun(cell, spans.events, t0, t_end, dt, device["kind"],
                       e2e_values)
        metrics = {}
        for m in cell.per_layer:
            v = reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device["busy_s"] = dt.busy_s(t0, t_end)
        device["window_s"] = t_end - t0
        result["breakdown"] = run.breakdown()
        notes.append(f"trace: busy {device['busy_s']:.6f} s of "
                     f"{device['window_s']:.6f} s, {len(spans.events)} spans")
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {k: {"value": readings[k], "limit": limits[k]}
                        for k in limits}
    checks = [f"check {k}: {readings[k]!r} (limit {limits[k]!r})"
              for k in limits]
    checks.append(f"correct: {bool(correct)}")
    evidence = {"params": params, "pool": pool, "ep": ep, "batches": batches,
                "answers": answers, "model": model, "readings": readings}
    return Outcome(result, notes, checks, evidence)


def control_readings(cell: Cell, ev: Dict[str, Any], control: str
                     ) -> Dict[str, float]:
    """The readings the comparison gives when the reference, computed one
    step lower in precision (``control``), stands in the program's place
    for the same batches and requests."""
    cfg, model, params = cell.cfg, ev["model"], ev["params"]
    ep, pool = ev["ep"], ev["pool"]
    n_way = int(cfg["n_way"])

    def sims_of(variant):
        sup = model.features(params, ep["support_x"], cfg, variant)
        protos = model.prototypes(sup, ep["support_y"], n_way)
        return model.sims(model.features(params, pool[:, 0], cfg, variant),
                          protos)

    ref_sims, ctl_sims = sims_of(None), sims_of(control)
    batches = [(x, model.features(params, x, cfg, control))
               for x, _ in ev["batches"]]
    answers = [(frames, ctl_sims[frames], [int(i) for i in
                                          ctl_sims[frames].argmax(axis=1)])
               for frames, _, _ in ev["answers"]]
    readings, _ = compare(batches, answers,
                          lambda x: model.features(params, x, cfg), ref_sims,
                          n_way, float(cfg["id_margin"]),
                          float(cfg["limits"]["sim_gap"]))
    return readings


# ---------------------------------------------------------------------------
# what the per-layer readers get
# ---------------------------------------------------------------------------
# What the engine's worker was doing, most specific first: waiting for the
# backbone's output, launching the backbone call, the classify head,
# resolving the answers, filling the batch; then the enclosing spans (the
# call, the answers, waiting for stragglers, building the batch); else a
# request waiting in the queue.
GAP_PRIORITY = ("serve.exec.wait", "serve.exec.dispatch", "serve.head",
                "serve.fulfil", "serve.fill", "serve.exec", "serve.respond",
                "serve.coalesce", "serve.batch", "serve.queue")


class TraceRun:
    """One traced window: the program's spans and the device trace, both on
    the ``perf_counter`` clock, with the cell they came from."""

    def __init__(self, cell: Cell, events: List[Dict], t0: float,
                 t_end: float, device: "devtrace.DeviceTrace", kind: str,
                 e2e: Dict[str, float]):
        self.cell = cell
        self.cfg = cell.cfg
        self.t0, self.t_end = t0, t_end
        self.window_s = t_end - t0
        self.device = device
        self.kind = kind
        self.e2e = e2e
        self.events = [e for e in events
                       if t0 <= e["t0"] and e["t0"] + e["dur_ms"] * 1e-3 <= t_end]

    def peaks(self) -> Dict[str, float]:
        return peaks(self.kind)

    def model(self):
        """The configuration's plain reference, which counts its work."""
        return model_module(self.cfg, self.cell.root)

    def spans(self, name: str) -> List[Dict]:
        return [e for e in self.events if e["name"] == name]

    def batches(self) -> List[Dict]:
        """One ``serve.batch`` span per backbone call in the window."""
        return self.spans("serve.batch")

    def intervals(self, name: str) -> List[tuple]:
        return [(e["t0"], e["t0"] + e["dur_ms"] * 1e-3)
                for e in self.spans(name)]

    def breakdown(self) -> Dict[str, List]:
        ops = self.device.op_seconds(self.t0, self.t_end)
        host = [(e["name"], e["t0"], e["t0"] + e["dur_ms"] * 1e-3)
                for e in self.events]
        gaps = devtrace.name_gaps(self.device.idle_gaps(self.t0, self.t_end),
                                  host, GAP_PRIORITY)
        return {"device_ops": devtrace.top(ops), "idle_gaps": devtrace.top(gaps)}


def emit(outcome: Outcome) -> None:
    out, err = sys.stdout, sys.stderr
    for line in outcome.notes:
        print(line, file=out, flush=True)
    for line in outcome.checks:
        print(line, file=err, flush=True)
    print(json.dumps(outcome.result), file=out, flush=True)
