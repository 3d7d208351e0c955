"""``repro.compile()`` — lower a QAT graph to a jitted deployment artifact.

The top of the compiler stack (DESIGN.md): pick a :class:`BuildRecipe`,
stream the graph through the :class:`PassManager` (precondition-checked,
optionally golden-IO-verified per pass), then lower the HW-mapped graph to a
**single jitted callable**:

* initializers (quantized weights, threshold tables) are closed over as
  constants — XLA folds and lays them out once at compile time;
* each node dispatches through the kernel table from
  :func:`repro.kernels.ops.graph_op_impls` (Pallas MVAU / GlobalAccPool) or
  the interpreter executors for pure data-movement ops;
* the whole network traces into ONE program, replacing the per-node Python
  interpreter loop (``graph.execute``) on the hot path — that loop re-traces
  and re-dispatches every op on every call, which is the dominant serving
  cost on CPU (measured in ``benchmarks/compile_bench.py``).

The artifact is a :class:`DeployedModel`: call it like a function on batched
inputs; ``.apply`` is the raw un-jitted function for composition under
``jax.vmap`` / ``jax.jit`` of a larger program; ``.trace`` holds the per-pass
build report.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import recipes as R
from repro.core.graph import _EXECUTORS, Graph, GraphBuildError
from repro.core.passes import PassManager, PassTrace

__all__ = ["DeployedModel", "bucket_for", "compile", "lower_graph",
           "normalize_buckets", "pow2_buckets"]


def lower_graph(graph: Graph, interpret: Optional[bool] = None) -> Callable:
    """Close a (streamlined) graph over its initializers and return a pure
    ``(*inputs) -> tuple(outputs)`` function, ready for ``jax.jit``/``vmap``.
    """
    from repro.kernels import ops as kops

    impls = dict(_EXECUTORS)
    impls.update(kops.graph_op_impls(interpret))
    missing = sorted({n.op for n in graph.nodes if n.op not in impls})
    if missing:
        raise GraphBuildError(f"cannot lower graph '{graph.name}': no "
                              f"implementation for ops {missing}")
    consts = {k: jnp.asarray(v) for k, v in graph.initializers.items()}
    nodes = [n.copy() for n in graph.nodes]       # freeze against later edits
    input_names = tuple(graph.inputs)
    output_names = tuple(graph.outputs)

    def apply_fn(*inputs):
        if len(inputs) != len(input_names):
            raise TypeError(f"graph '{graph.name}' takes {len(input_names)} "
                            f"input(s) {input_names}, got {len(inputs)}")
        env: Dict[str, jax.Array] = dict(consts)
        env.update(zip(input_names, inputs))
        for node in nodes:
            out = impls[node.op](node, *[env[i] for i in node.inputs])
            outs = out if isinstance(out, (tuple, list)) else (out,)
            for name, val in zip(node.outputs, outs):
                env[name] = val
        return tuple(env[o] for o in output_names)

    return apply_fn


def bucket_for(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket >= n. Buckets bound the set of batch shapes that ever
    reach the jitted program, so the executable cache stays finite."""
    if n <= 0:
        raise ValueError(f"batch size must be positive, got {n}")
    fit = [b for b in buckets if b >= n]
    if not fit:
        raise ValueError(f"batch {n} exceeds largest bucket "
                         f"{max(buckets)}; raise max_batch / split upstream")
    return min(fit)


def pow2_buckets(max_batch: int) -> Tuple[int, ...]:
    """(1, 2, 4, ..., max_batch) — max_batch is included even off-power."""
    bs = []
    b = 1
    while b < max_batch:
        bs.append(b)
        b *= 2
    bs.append(max_batch)
    return tuple(bs)


def normalize_buckets(buckets: Sequence[int]) -> Tuple[int, ...]:
    """Dedup + sort a bucket list into the canonical tuple; rejects empty
    lists and non-positive or non-integral sizes (a float bucket would
    otherwise surface much later as a bogus pad length)."""
    bs = set()
    for b in buckets:
        if int(b) != b or int(b) < 1:
            raise ValueError(f"buckets must be positive ints, got {buckets!r}")
        bs.add(int(b))
    if not bs:
        raise ValueError("buckets must be non-empty")
    return tuple(sorted(bs))


@dataclasses.dataclass
class DeployedModel:
    """A compiled, executable deployment artifact.

    ``__call__`` runs the jitted program (returns a single array when the
    graph has a single output).  ``apply`` is the raw traced function —
    ``jax.vmap(dm.apply)`` batches over a leading axis, and embedding
    ``dm.apply`` inside a larger jitted program fuses it with the caller.

    ``jax.jit`` keys its executable cache on input shape, so every new batch
    size silently RETRACES the whole program mid-flight — fatal for a
    serving loop with arbitrary request sizes.  ``warmup(buckets, example)``
    pre-compiles a fixed set of padded batch shapes and ``batched(x)`` pads
    any batch up to its bucket and slices the result back, so steady-state
    serving never traces again (``trace_count`` proves it).
    """

    graph: Graph
    recipe_name: str
    trace: PassTrace
    apply: Callable
    input_names: Tuple[str, ...]
    output_names: Tuple[str, ...]
    datapath: str = "f32"
    # the resolved pass list that built self.graph — part of fingerprint(),
    # so artifacts built with and without (say) fuse_integer_datapath can
    # never alias in a persistent CompileCache
    pass_names: Tuple[str, ...] = ()
    # the Pallas interpret decision lower_graph() baked into ``apply``
    # (None = auto: interpreted off-TPU) — dispatch_table() reports from it
    interpret: Optional[bool] = None
    _jitted: Optional[Callable] = None
    _buckets: Optional[Tuple[int, ...]] = None
    _trace_count: int = 0
    # AOT executable cache: (input shape, dtype name) -> jax.stages.Compiled.
    # Populated by warmup() (freshly lowered or restored from a persistent
    # CompileCache); __call__/batched dispatch here first so a cache-restored
    # replica never traces at all.
    _exec: Dict[Tuple[Tuple[int, ...], str], Any] = \
        dataclasses.field(default_factory=dict)
    # per-bucket cold-start log: [{"bucket", "seconds", "cached", "key"}]
    compile_log: list = dataclasses.field(default_factory=list)
    _fingerprint: Optional[str] = None

    def __post_init__(self):
        base = self.apply

        def counted(*inputs):
            # Body runs only while TRACING under jit (or eagerly, if called
            # raw) — steady-state jitted calls replay the compiled
            # executable and never touch this counter.
            self._trace_count += 1
            return base(*inputs)

        self.apply = counted
        if self._jitted is None:
            self._jitted = jax.jit(counted)

    @property
    def trace_count(self) -> int:
        """How many times the program body was traced (or run eagerly).
        Flat after ``warmup`` == the serving loop never recompiles."""
        return self._trace_count

    @property
    def buckets(self) -> Optional[Tuple[int, ...]]:
        return self._buckets

    def fingerprint(self) -> str:
        """Content digest of (graph structure + initializer bytes, datapath,
        build pass set) — the artifact half of a
        :class:`repro.ckpt.CompileCache` key.  The pass set matters even
        though the post-pass graph is already hashed: it closes the
        stale-cache hazard where a new pass (e.g. ``fuse_integer_datapath``)
        happens to leave some graph unchanged structurally but changes what
        the executors dispatch — two artifacts that were built differently
        must never alias to the same persisted executable."""
        if self._fingerprint is None:
            import hashlib

            from repro.ckpt.compile_cache import graph_fingerprint

            pd = hashlib.sha256(
                "|".join(self.pass_names).encode()).hexdigest()[:8]
            self._fingerprint = (f"{graph_fingerprint(self.graph)}-"
                                 f"{self.datapath}-{pd}")
        return self._fingerprint

    def _exec_key(self, shape: Tuple[int, ...], dtype) -> Tuple[Tuple[int, ...], str]:
        return (tuple(int(s) for s in shape), np.dtype(dtype).name)

    def warmup(self, buckets: Sequence[int],
               example: Union[jax.Array, np.ndarray], *,
               cache: Optional[Any] = None,
               metrics: Optional[Any] = None,
               label: Optional[str] = None) -> Tuple[int, ...]:
        """Pre-compile one executable per padded batch bucket.

        ``example`` is a BATCHED input of any batch size (same rank as what
        ``__call__`` takes) — its trailing dims/dtype define the per-sample
        shape.  Returns the sorted bucket tuple now backing :meth:`batched`.

        Each bucket lowers AOT (``jit(...).lower(x).compile()``) into a
        per-shape executable table that ``__call__``/``batched`` dispatch
        through.  With a :class:`repro.ckpt.CompileCache`, executables are
        restored from disk instead of recompiled (zero traces — a restarted
        replica's cold start collapses from seconds to milliseconds), and
        fresh compiles are published back for the next restart.  A bucket
        already warmed in-process is skipped outright — re-warming a shared
        artifact (a second engine replica over the same registry) is free.

        Per-bucket compile wall-clock lands in :attr:`compile_log` and, when
        a ``metrics`` (:class:`repro.serve.ServeMetrics`) is given, in its
        compile counters — cold-start cost is observable with or without
        the cache.
        """
        if len(self.input_names) != 1:
            return self._warmup_multi(buckets, example, cache=cache,
                                      metrics=metrics, label=label)
        ex = jnp.asarray(example)
        if ex.ndim < 1:
            raise ValueError("example must be batched (leading batch axis)")
        sample = ex[0]
        bs = normalize_buckets(buckets)
        name = label or self.graph.name
        for b in bs:
            shape = (b,) + sample.shape
            ekey = self._exec_key(shape, sample.dtype)
            if ekey in self._exec:
                continue
            x = jnp.zeros(shape, sample.dtype)
            if cache is not None:
                ckey = cache.key(kind="deployed-model",
                                 graph=self.fingerprint(),
                                 shape=list(shape),
                                 dtype=np.dtype(sample.dtype).name)
                exe, hit, dt = cache.get_or_compile(
                    ckey, lambda x=x: self._jitted.lower(x).compile(),
                    meta={"artifact": name, "bucket": int(b)})
            else:
                ckey, hit = None, False
                t0 = time.perf_counter()
                exe = self._jitted.lower(x).compile()
                dt = time.perf_counter() - t0
            self._exec[ekey] = exe
            self.compile_log.append({"bucket": int(b), "seconds": dt,
                                     "cached": hit, "key": ckey})
            if metrics is not None:
                metrics.record_compile(name, int(b), dt, cached=hit)
        self._buckets = bs
        return bs

    def _warmup_multi(self, buckets: Sequence[int], example, *,
                      cache: Optional[Any] = None,
                      metrics: Optional[Any] = None,
                      label: Optional[str] = None) -> Tuple[int, ...]:
        """Multi-input warmup (e.g. the decode graph's (tokens, pos, k*, v*)):
        ``example`` is one BATCHED array per graph input, in input order.
        Every input is padded along the shared leading batch axis, so one
        bucket still means one executable; non-batch dims (KV capacity)
        vary by calling warmup once per capacity."""
        if not isinstance(example, (tuple, list)) \
                or len(example) != len(self.input_names):
            raise ValueError(
                f"multi-input graph '{self.graph.name}' needs one batched "
                f"example per input {self.input_names}")
        samples = [jnp.asarray(e) for e in example]
        if any(sm.ndim < 1 for sm in samples):
            raise ValueError("examples must be batched (leading batch axis)")
        bs = normalize_buckets(buckets)
        name = label or self.graph.name
        for b in bs:
            xs = [jnp.zeros((b,) + tuple(sm.shape[1:]), sm.dtype)
                  for sm in samples]
            ekey = tuple(self._exec_key(x.shape, x.dtype) for x in xs)
            if ekey in self._exec:
                continue
            if cache is not None:
                ckey = cache.key(kind="deployed-model",
                                 graph=self.fingerprint(),
                                 shape=[list(x.shape) for x in xs],
                                 dtype=[np.dtype(x.dtype).name for x in xs])
                exe, hit, dt = cache.get_or_compile(
                    ckey, lambda xs=xs: self._jitted.lower(*xs).compile(),
                    meta={"artifact": name, "bucket": int(b)})
            else:
                ckey, hit = None, False
                t0 = time.perf_counter()
                exe = self._jitted.lower(*xs).compile()
                dt = time.perf_counter() - t0
            self._exec[ekey] = exe
            self.compile_log.append({"bucket": int(b), "seconds": dt,
                                     "cached": hit, "key": ckey})
            if metrics is not None:
                metrics.record_compile(name, int(b), dt, cached=hit)
        self._buckets = bs
        return bs

    def batched(self, x: Union[jax.Array, np.ndarray]):
        """Run a batch through the bucket-padded executable cache: pad the
        leading axis up to the nearest warmed bucket, execute, slice back.
        Valid because every op in the HW graph is per-sample independent
        (im2col/matmul/threshold/pool/GAP never mix batch rows)."""
        if self._buckets is None:
            raise RuntimeError("call warmup(buckets, example) before "
                               "batched() — unpadded shapes retrace per size")
        x = jnp.asarray(x)
        n = x.shape[0]
        b = bucket_for(n, self._buckets)
        if b != n:
            pad = [(0, b - n)] + [(0, 0)] * (x.ndim - 1)
            x = jnp.pad(x, pad)
        outs = self._dispatch(x)
        outs = tuple(o[:n] for o in outs)
        return outs[0] if len(self.output_names) == 1 else outs

    def _dispatch(self, x):
        """Route through the AOT executable for this exact shape when warmup
        built one (never traces — the cache-restored cold-start path), else
        fall back to the jit cache."""
        exe = self._exec.get(self._exec_key(jnp.shape(x), x.dtype))
        return exe(x) if exe is not None else self._jitted(x)

    def __call__(self, *inputs, **feeds):
        if feeds:
            try:
                args = tuple(feeds[n] for n in self.input_names)
            except KeyError as e:
                raise TypeError(f"missing graph input {e}; expected "
                                f"{self.input_names}") from None
            if inputs:
                raise TypeError("pass inputs positionally or by name, not both")
        else:
            args = inputs
        if (len(args) == 1 and self._exec and hasattr(args[0], "shape")
                and not isinstance(args[0], jax.core.Tracer)):
            outs = self._dispatch(jnp.asarray(args[0]))
        elif (len(args) > 1 and self._exec
              and all(hasattr(a, "shape")
                      and not isinstance(a, jax.core.Tracer) for a in args)):
            xs = [jnp.asarray(a) for a in args]
            ekey = tuple(self._exec_key(x.shape, x.dtype) for x in xs)
            exe = self._exec.get(ekey)
            outs = exe(*xs) if exe is not None else self._jitted(*xs)
        else:
            outs = self._jitted(*args)
        return outs[0] if len(self.output_names) == 1 else outs

    def op_counts(self) -> Dict[str, int]:
        from repro.core.passes import op_histogram

        return op_histogram(self.graph)

    def dispatch_table(self) -> list:
        """Per-node kernel dispatch: ``[{"tensor", "op", "kernel"}]``.

        ``kernel`` comes from :func:`repro.kernels.ops.kernel_dispatch` —
        the same decision function the deployed executors run — so a fusion
        regression (a node silently falling back to ``ref-oracle``) is
        visible here without a profiler."""
        from repro.kernels import ops as kops

        emulated = (kops.default_interpret() if self.interpret is None
                    else self.interpret)
        rows = []
        for n in self.graph.nodes:
            n_levels = None
            if n.op == "mvau_int" and n.inputs[-1] in self.graph.initializers:
                n_levels = int(np.asarray(
                    self.graph.initializers[n.inputs[-1]]).shape[-1])
            rows.append({"tensor": n.outputs[0], "op": n.op,
                         "kernel": kops.kernel_dispatch(n, emulated,
                                                        n_levels)})
        return rows

    def profile(self, example, *, xla: bool = True,
                device_kind: Optional[str] = None) -> Dict[str, Any]:
        """Per-node FLOPs/bytes/estimated-ms attribution for one batch
        shape, cross-checked against XLA's ``cost_analysis()`` totals —
        see :func:`repro.obs.costmodel.profile_deployed`.  The farm records
        ``totals.est_ms`` into sweep points as ``modeled_ms``."""
        from repro.obs.costmodel import profile_deployed

        return profile_deployed(self, example, xla=xla,
                                device_kind=device_kind)

    def qdq_counts(self) -> Dict[str, int]:
        """Surviving quantize/dequantize nodes and interior round-trip pairs.

        ``interior_pairs`` counts quantize nodes fed directly by a
        dequantize — exactly the structure ``fuse_integer_datapath`` folds
        into ``requantize``.  A fused artifact must report 0 (asserted in
        tests and in BENCH_pr7)."""
        q = dq = pairs = 0
        for n in self.graph.nodes:
            if n.op == "quantize":
                q += 1
                p = self.graph.producer(n.inputs[0])
                if p is not None and p.op == "dequantize":
                    pairs += 1
            elif n.op == "dequantize":
                dq += 1
        return {"quantize": q, "dequantize": dq, "interior_pairs": pairs}

    def weight_bytes(self) -> int:
        """Measured storage bytes across all baked-in constants (weight
        codes, threshold tables) — the HBM/BRAM footprint the paper's
        bit-width lever shrinks.  Packed int4 counts at packed density
        because the packed array IS what is stored."""
        return int(sum(np.asarray(v).nbytes
                       for v in self.graph.initializers.values()))

    def throughput(self, *inputs, iters: int = 20) -> Dict[str, float]:
        """Measured wall-clock of the jitted program on BATCHED ``inputs``
        (leading axis = batch; an unbatched sample would report its first
        dim as the batch size): ``{"ms_per_call", "calls_per_s", "batch",
        "bucket"}`` (simple mean after a warm-up call, like
        benchmarks/compile_bench.py).  ``bucket`` is the padded bucket the
        measurement would serve through (equal to ``batch`` when no buckets
        are warmed or the batch exceeds them) — so a reported number is
        attributable to ONE executable in the bucket cache."""
        n = int(jnp.shape(inputs[0])[0]) if inputs and jnp.ndim(inputs[0]) else 1
        if self._exec and len(inputs) >= 1:
            run = self.__call__          # AOT bucket dispatch, single or multi
        else:
            run = self._jitted
        jax.block_until_ready(run(*inputs))              # warm-up / compile
        t0 = time.perf_counter()
        for _ in range(max(iters, 1)):
            out = run(*inputs)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / max(iters, 1)
        # a batch beyond the warmed buckets still measures fine (jit takes
        # any shape) — it just isn't attributable to a cached bucket
        bucket = (bucket_for(n, self._buckets)
                  if self._buckets and n <= self._buckets[-1] else n)
        return {"ms_per_call": dt * 1e3, "calls_per_s": 1.0 / dt,
                "batch": float(n), "bucket": float(bucket)}

    def report(self, sample_input=None, iters: int = 20) -> str:
        ops = ", ".join(f"{k}×{v}" for k, v in sorted(self.op_counts().items()))
        head = (f"DeployedModel('{self.graph.name}', recipe='{self.recipe_name}', "
                f"datapath='{self.datapath}', {len(self.graph.nodes)} nodes: "
                f"{ops})\n  weight storage: {self.weight_bytes()} bytes")
        qdq = self.qdq_counts()
        head += (f"\n  quantize/dequantize surviving: {qdq['quantize']}/"
                 f"{qdq['dequantize']} (interior pairs: "
                 f"{qdq['interior_pairs']})")
        head += "\n  kernel dispatch:"
        for row in self.dispatch_table():
            head += (f"\n    {row['tensor']:28s} {row['op']:20s} "
                     f"-> {row['kernel']}")
        if sample_input is not None:
            t = self.throughput(sample_input, iters=iters)
            head += (f"\n  measured: {t['ms_per_call']:.2f} ms/call "
                     f"({t['calls_per_s']:.1f} calls/s) on "
                     f"{jax.default_backend()}")
        return head + "\n" + self.trace.report()


def _check_attention_kernels(graph: Graph, feeds: Dict[str, Any],
                             interpret: Optional[bool]) -> None:
    """Golden check of every ``attn_full`` node: the Pallas kernel (compiled
    on a TPU, interpreted elsewhere) against the interpreter's oracle, on
    the inputs the interpreter fed that node.  The attention is integer, so
    the two agree bit for bit; graphs without attention are not touched."""
    from repro.core.graph import execute
    from repro.kernels import ops as kops

    nodes = [n for n in graph.nodes if n.op == "attn_full"]
    if not nodes:
        return
    probe = graph.copy()
    probe.outputs = [n.inputs[0] for n in nodes] + [n.outputs[0] for n in nodes]
    vals = execute(probe, feeds)
    for node, qkv, want in zip(nodes, vals, vals[len(nodes):]):
        got = np.asarray(kops.attn_full(qkv, node.attrs["heads"],
                                        node.attrs["frac_bits"],
                                        interpret=interpret))
        diff = int(np.sum(got != np.asarray(want)))
        if diff:
            raise GraphBuildError(
                f"attention kernel of '{node.outputs[0]}' in graph "
                f"'{graph.name}' departs from its oracle in {diff} of "
                f"{got.size} outputs")


def compile(graph_or_model: Any, qcfg: Any = None, *,
            recipe: Union[str, R.BuildRecipe],
            datapath: str = "f32",
            fuse: bool = True,
            sample_input: Optional[jax.Array] = None,
            verify_feeds: Optional[Dict[str, Any]] = None,
            interpret: Optional[bool] = None,
            rtol: float = 1e-5, atol: float = 1e-6,
            tracer: Optional[Any] = None) -> DeployedModel:
    """Build a :class:`DeployedModel` from a graph or a native model object.

    Args:
      graph_or_model: a :class:`Graph` (e.g. from ``resnet9.export_graph``),
        or the recipe's native model object (a ResNet-9 param tree for
        ``recipe="resnet9"``) if the recipe registered an ``exporter``.
      qcfg: the :class:`QuantConfig` — forwarded to the exporter; unused when
        a pre-exported graph is given.
      recipe: registered recipe name or a :class:`BuildRecipe` — required,
        because the pass list is architecture-dependent (the paper's core
        point): silently defaulting would mis-build foreign graphs.
      datapath: ``"f32"`` executes the HW graph in float emulation of the
        fixed-point grid (the QAT view); ``"int"`` appends the
        ``infer_datatypes`` + ``lower_to_integer_datapath`` passes
        (core/datatypes.py) so weights ship as integer codes at their
        narrowest storage dtype and MVAUs run the integer compare-count
        datapath — bit-for-bit equal to ``"f32"`` on the grid, with the
        storage/bandwidth footprint of the paper's hardware.
      fuse: with ``datapath="int"``, additionally run
        ``fuse_integer_datapath``: matmul/threshold chains collapse into
        fused ``mvau_int`` nodes, interior dequantize→quantize pairs fold
        into integer ``requantize``, and threshold tables are sorted —
        activations stay narrow integer codes end-to-end and the fast
        integer kernels engage.  ``fuse=False`` keeps the unfused lowering
        (the differential-testing baseline).  Ignored for ``"f32"``.
      sample_input: optional golden input for FINN-style per-pass IO
        verification (single-input graphs; use ``verify_feeds`` otherwise) —
        covers the integer lowering stage too, and then checks each
        attention kernel against the interpreter's oracle, bit for bit
        (:func:`_check_attention_kernels`).
      interpret: force Pallas interpret mode (default: auto — interpreted
        off-TPU, compiled on TPU).
      tracer: optional :class:`repro.obs.Tracer` for compiler telemetry
        (per-pass spans); default is the process-global tracer, a no-op
        until ``repro.obs.configure()`` attaches an exporter.

    Raises :class:`~repro.core.passes.PassOrderError` on mis-ordered
    recipes, :class:`~repro.core.passes.PassVerificationError` if a pass
    breaks golden-IO equivalence, and
    :class:`~repro.core.graph.GraphBuildError` if the streamlined graph is
    not HW-mappable.
    """
    if datapath not in ("f32", "int"):
        raise ValueError(f"datapath must be 'f32' or 'int', got {datapath!r}")
    rec = R.recipe(recipe) if isinstance(recipe, str) else recipe
    if isinstance(graph_or_model, Graph):
        graph = graph_or_model
    elif rec.exporter is not None:
        graph = rec.exporter(graph_or_model, qcfg)
    else:
        raise TypeError(
            f"recipe '{rec.name}' has no exporter; pass a Graph (got "
            f"{type(graph_or_model).__name__})")
    if sample_input is not None and verify_feeds is None:
        if len(graph.inputs) != 1:
            raise ValueError("sample_input needs a single-input graph; use "
                             "verify_feeds for multi-input graphs")
        verify_feeds = {graph.inputs[0]: sample_input}

    passes = list(rec.passes)
    if datapath == "int":
        passes += ["infer_datatypes", "lower_to_integer_datapath"]
        if fuse:
            passes.append("fuse_integer_datapath")
    result = PassManager(rtol=rtol, atol=atol, tracer=tracer).run(
        graph, passes, verify_feeds=verify_feeds)
    hw = result.graph
    if verify_feeds is not None:
        _check_attention_kernels(hw, verify_feeds, interpret)
    from repro.core.passes import resolve_pass

    return DeployedModel(
        graph=hw, recipe_name=rec.name, trace=result.trace,
        apply=lower_graph(hw, interpret),
        input_names=tuple(hw.inputs), output_names=tuple(hw.outputs),
        datapath=datapath,
        pass_names=tuple(resolve_pass(p).name for p in passes),
        interpret=interpret)
