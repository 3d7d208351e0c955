"""Chip smoke run: the main path once, on a TPU, through the user entry points.

  python chip_smoke.py             # one chip: every phase below
  python chip_smoke.py --chips 4   # four chips: the sharded cluster head only

One chip, all in this process (a chip serves one process at a time):

1. ``pretrain_backbone``: a few QAT steps of resnet9 at the paper's width
   (64 channels, 32x32 frames, w6a4 — ``configs/resnet9_paper.py``).
2. ``FSLPipeline.deploy`` at ``datapath="int"`` and ``"f32"`` into an
   ``ArtifactRegistry``.  Every ``mvau_int`` must dispatch to the fused
   Pallas kernel and the GAP to its Pallas kernel.
3. ``ServeEngine``: warmup, 5 novel classes registered from 5 shots each,
   15 queries classified one frame at a time and as one batch.  The int and
   f32 artifacts must agree on every class id, and the int artifact's
   features must equal, bit for bit, the same graph compiled off-TPU and
   run on the host CPU (integer arithmetic: any difference is a bug).
4. lm-tiny greedy decode of seeded prompts through ``ServeEngine`` with
   ``DecodeAdapter``, int and f32 artifacts; the tokens must equal those of
   the int artifact compiled off-TPU and stepped on the host CPU.
5. The Pallas kernels the main path does not reach (packed int4 weights,
   255-level thresholds, batched GAP, int4 ``qmatmul``), each equal to its
   interpret-mode run on the host CPU.

``--chips 4`` runs only ``ServeCluster`` over a ``ShardedNCMHead`` whose
prototype rows are split across the four chips, checks that the rows land
on four devices, and compares its similarities with the serial head on one
chip.

Earlier lines report the device, seconds per phase (compile apart from
run where the API separates them), dispatch counts and differences.  The
last line is ``{"ok": true, "device": {...}}``.  Without a TPU the script
exits non-zero and prints no result.  Weights and data come from ``--seed``.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

WIDTH = 64               # configs/resnet9_paper.py
IMG = 32
PRETRAIN_STEPS = 3
N_WAY, K_SHOT, QUERIES_PER_WAY = 5, 5, 3    # 15 queries
MAX_BATCH = 16
DECODE_PROMPTS, PROMPT_LEN, NEW_TOKENS = 4, 5, 8


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  check ok: {what}", flush=True)


@contextlib.contextmanager
def timed(label: str, seconds: dict):
    t0 = time.perf_counter()
    yield
    seconds[label] = time.perf_counter() - t0
    print(f"  {label}: {seconds[label]:.3f} s", flush=True)


def kernel_counts(dm) -> collections.Counter:
    return collections.Counter((r["op"], r["kernel"])
                               for r in dm.dispatch_table())


def _on_cpu():
    import jax

    return jax.default_device(jax.devices("cpu")[0])


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------
def fsl_phases(seed: int, seconds: dict) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import resnet9_paper
    from repro.core.deploy import lower_graph
    from repro.data.synthetic import SyntheticImages
    from repro.fsl.pipeline import FSLPipeline, pretrain_backbone
    from repro.serve import ArtifactRegistry, ServeEngine

    data = SyntheticImages(n_base=16, n_novel=N_WAY + 1, seed=seed, img=IMG)
    pipe = FSLPipeline(width=WIDTH, qcfg=resnet9_paper.QUANT)

    print(f"phase pretrain: resnet9 width {WIDTH}, {IMG}x{IMG}, w6a4, "
          f"{PRETRAIN_STEPS} QAT steps", flush=True)
    with timed("pretrain compile+run", seconds):
        out = pretrain_backbone(data, pipe, steps=PRETRAIN_STEPS, batch=32,
                                seed=seed)
    print(f"  losses {out['losses']}")
    check(bool(np.all(np.isfinite(out["losses"]))), "pretrain losses finite")
    params = out["params"]

    print("phase deploy: int and f32 artifacts", flush=True)
    registry = ArtifactRegistry()
    with timed("deploy passes (host)", seconds):
        registry.register("w6a4-int", pipe.deploy(params, datapath="int"),
                          default=True)
        registry.register("f32", pipe.deploy(params, datapath="f32"))
    dm_int = registry.get("w6a4-int").feats.deployed_model
    dm_f32 = registry.get("f32").feats.deployed_model
    k_int, k_f32 = kernel_counts(dm_int), kernel_counts(dm_f32)
    print(f"  int dispatch: {dict(k_int)}")
    print(f"  f32 dispatch: {dict(k_f32)}")
    check(k_int[("mvau_int", "fused-pallas")] == 8
          and sum(v for (op, _), v in k_int.items() if op == "mvau_int") == 8,
          "int artifact: all 8 mvau_int on fused-pallas")
    check(k_int[("global_acc_pool", "pallas")] == 1
          and k_f32[("global_acc_pool", "pallas")] == 1, "GAP on pallas")
    fallbacks = [k for k in list(k_int) + list(k_f32)
                 if k[1] in ("ref-oracle", "f32-gemm")]
    check(not fallbacks, f"no ref-oracle or f32-gemm node {fallbacks}")

    print("phase serve: ServeEngine warmup, register, classify", flush=True)
    episode = data.episode(np.random.default_rng(seed + 1), n_way=N_WAY,
                           k_shot=K_SHOT, n_query=QUERIES_PER_WAY)
    queries = episode["query_x"]
    with ServeEngine(registry, max_batch=MAX_BATCH, batch_wait_ms=2.0) as eng:
        with timed("engine warmup (compile)", seconds):
            eng.warmup(img=IMG)
        traces = eng.trace_counts()
        with timed("register 5 classes x 2 artifacts", seconds):
            for way in range(N_WAY):
                shots = episode["support_x"][episode["support_y"] == way]
                for art in registry.names():
                    eng.submit_register(f"novel{way}", shots,
                                        artifact=art).result(120)
        # the head's programs at this class count, for every query bucket
        primed = eng.warmup(img=IMG)
        head = ServeEngine.HEAD_TRACES
        check({**primed, head: traces[head]} == traces,
              "no backbone trace from registers")
        single, batch = {}, {}
        with timed("classify 15 single frames x 2 artifacts", seconds):
            for art in registry.names():
                single[art] = [eng.submit_classify(q[None], artifact=art)
                               .result(120) for q in queries]
        with timed("classify one batch of 15 x 2 artifacts", seconds):
            for art in registry.names():
                batch[art] = eng.submit_classify(queries,
                                                 artifact=art).result(120)
        print(f"  traces: {traces} at warmup, {primed} with the classes "
              f"registered, {eng.trace_counts()} after")
        check(eng.trace_counts() == primed, "no trace after warmup")
    for art in registry.names():
        ids_single = [r.class_ids[0] for r in single[art]]
        sims_single = np.concatenate([r.sims for r in single[art]])
        acc = np.mean([c == f"novel{y}" for c, y in
                       zip(batch[art].class_ids, episode["query_y"])])
        print(f"  {art}: episode accuracy {acc:.3f}; single vs batch max "
              f"|dsim| {np.abs(sims_single - batch[art].sims).max():.3g}")
        check(ids_single == batch[art].class_ids,
              f"{art}: single-frame and batched class ids agree")
    d_sim = np.abs(batch["w6a4-int"].sims - batch["f32"].sims).max()
    print(f"  int vs f32 max |dsim| {d_sim:.3g}")
    check(batch["w6a4-int"].class_ids == batch["f32"].class_ids,
          "int and f32 class ids agree")

    print("phase reference: int graph off-TPU on the host CPU", flush=True)
    x = jnp.asarray(queries)
    with timed("int features on chip (compile+run)", seconds):
        chip = np.asarray(dm_int(x))
        chip_single = np.concatenate([np.asarray(dm_int(x[i:i + 1]))
                                      for i in range(len(queries))])
    with timed("int features on host CPU (compile+run)", seconds), _on_cpu():
        ref_apply = jax.jit(lower_graph(dm_int.graph, interpret=True))
        host = np.asarray(ref_apply(jnp.asarray(queries))[0])
    print(f"  features {chip.shape}, max |chip - cpu| "
          f"{np.abs(chip - host).max():.3g}")
    check(chip.shape == (len(queries), 8 * WIDTH)
          and np.isfinite(chip).all(), f"int features finite, {chip.shape}")
    check(np.array_equal(chip, host), "int features on chip == CPU, bitwise")
    check(np.array_equal(chip_single, chip),
          "int features one frame at a time == batched, bitwise")


def decode_phase(seed: int, seconds: dict) -> None:
    import jax
    import numpy as np

    import repro.configs.lm_tiny  # noqa: F401  (registers the arch)
    from repro.models import lm
    from repro.models.common import get_config
    from repro.serve import ArtifactRegistry, ServeEngine
    from repro.serve.decode import (DecodeAdapter, build_decode_artifact,
                                    greedy_generate)

    print("phase decode: lm-tiny greedy decode through ServeEngine",
          flush=True)
    cfg = get_config("lm-tiny")
    caps = (16,)
    params = lm.init_params(jax.random.PRNGKey(seed), cfg)
    with timed("decode deploy passes (host)", seconds):
        arts = {dp: build_decode_artifact(params, cfg, datapath=dp,
                                          capacities=caps)
                for dp in ("int", "f32")}
    k_int = kernel_counts(arts["int"].dm)
    print(f"  int dispatch: {dict(k_int)}")
    check(k_int[("mvau_int", "fused-pallas")] == 2
          and k_int[("matmul_int", "int8-dot")] > 0
          and not [k for k in k_int if k[1] in ("ref-oracle", "f32-gemm")],
          "decode int artifact: mvau_int on fused-pallas, matmul_int on "
          "int8-dot")

    reg = ArtifactRegistry()
    adapter = DecodeAdapter()
    reg.register("lm-int", arts["int"], adapter=adapter, default=True)
    reg.register("lm-f32", arts["f32"], adapter=adapter)
    rng = np.random.default_rng(seed)
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab, PROMPT_LEN)]
               for _ in range(DECODE_PROMPTS)]
    eng = ServeEngine(reg, max_batch=DECODE_PROMPTS,
                      buckets=(1, DECODE_PROMPTS))
    try:
        with timed("decode warmup (compile)", seconds):
            eng.warmup()
        tokens = {}
        for name in reg.names():
            with timed(f"{name} generate {DECODE_PROMPTS}x{NEW_TOKENS}",
                       seconds):
                tokens[name] = greedy_generate(eng, prompts, NEW_TOKENS,
                                               artifact=name)
    finally:
        eng.stop()

    with timed("decode reference on host CPU", seconds), _on_cpu():
        cpu = jax.devices("cpu")[0]
        ref = build_decode_artifact(jax.device_put(params, cpu), cfg,
                                    datapath="int", capacities=caps,
                                    interpret=True)
        want = [[ref.start_sequence(i, p)[0]] for i, p in enumerate(prompts)]
        for _ in range(NEW_TOKENS - 1):
            res, _ = ref.step_sequences([(i, None) for i in
                                         range(len(prompts))])
            for row, (_, tok, _, _) in zip(want, res):
                row.append(tok)
    print(f"  cpu reference tokens {want}")
    for name, got in tokens.items():
        print(f"  {name} tokens {got}")
        check(got == want, f"{name} decode tokens == CPU reference")


def kernel_phase(seed: int, seconds: dict) -> None:
    """Kernels off the resnet9 w6a4 main path, chip vs interpret mode."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import quant
    from repro.kernels import ops

    print("phase kernels: chip vs interpret mode on the host CPU",
          flush=True)
    rng = np.random.default_rng(seed)
    w4 = rng.integers(-8, 8, size=(4608, 512)).astype(np.int32)
    cases = {
        "mvau_int packed int4": (
            lambda interp, x, w, t: ops.mvau_int(x, w, t, interpret=interp,
                                                 w_packed=True),
            (rng.integers(0, 16, size=(64, 4608)).astype(np.int8),
             np.asarray(quant.pack_int4(jnp.asarray(w4))),
             np.sort(rng.integers(-30000, 30000, size=(512, 15)),
                     axis=1).astype(np.int32))),
        "mvau_int L=255": (
            lambda interp, x, w, t: ops.mvau_int(x, w, t, interpret=interp),
            (rng.integers(-128, 128, size=(8, 96)).astype(np.int8),
             rng.integers(-128, 128, size=(96, 64)).astype(np.int8),
             np.sort(rng.integers(-100000, 100000, size=(64, 255)),
                     axis=1).astype(np.int32))),
        "mvau f32 L=255": (
            lambda interp, x, w, t: ops.mvau(x, w, t, interpret=interp),
            (rng.integers(-128, 128, size=(8, 96)).astype(np.float32) / 16,
             rng.integers(-128, 128, size=(96, 64)).astype(np.float32) / 64,
             np.sort(rng.integers(-20000, 20000, size=(64, 255)),
                     axis=1).astype(np.float32) / 1024)),
        "gap int batch 8": (
            lambda interp, x: ops.gap(x, interpret=interp),
            (rng.integers(0, 16, size=(8, 4, 4, 512)).astype(np.int32),)),
        "qmatmul int4": (
            lambda interp, x, w, s: ops.qmatmul(x, w, s, bits=4,
                                                interpret=interp),
            (jnp.asarray(rng.integers(-8, 8, size=(8, 256)), jnp.bfloat16),
             np.asarray(quant.pack_int4(jnp.asarray(w4[:256, :256]))),
             np.ones((256,), np.float32))),
    }
    with timed("kernels chip vs interpret (compile+run)", seconds):
        for name, (fn, args) in cases.items():
            chip = np.asarray(jax.jit(lambda *a, fn=fn: fn(False, *a))(*args))
            with _on_cpu():
                host = np.asarray(fn(True, *[jax.device_put(
                    a, jax.devices("cpu")[0]) for a in args]))
            print(f"  {name}: {chip.shape} max |chip - interpret| "
                  f"{np.abs(chip.astype(np.float64) - host).max():.3g}")
            check(np.array_equal(chip, host), f"{name} bitwise")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def sharded_cluster_phase(seed: int, seconds: dict) -> None:
    import jax
    import numpy as np

    from repro.configs import resnet9_paper
    from repro.data.synthetic import SyntheticImages
    from repro.fsl.pipeline import FSLPipeline
    from repro.serve.cluster import ServeCluster
    from repro.serve.cluster.cluster import sharded_tenant_registry
    from repro.serve.cluster.sharded import ShardedNCMHead

    devices = jax.devices()
    n_way = 2 * len(devices)               # two prototype rows per chip
    print(f"phase cluster: ServeCluster, NCM head sharded over "
          f"{len(devices)} chips", flush=True)
    data = SyntheticImages(n_base=4, n_novel=n_way, seed=seed, img=IMG)
    pipe = FSLPipeline(width=WIDTH, qcfg=resnet9_paper.QUANT)
    params = pipe._hooks().init_params(jax.random.PRNGKey(seed), WIDTH)
    registry = sharded_tenant_registry()
    with timed("deploy passes (host)", seconds):
        feats = pipe.deploy(params, datapath="int")
    registry.register_backbone("w6a4-int", feats, default=True)
    episode = data.episode(np.random.default_rng(seed + 1), n_way=n_way,
                           k_shot=K_SHOT, n_query=MAX_BATCH // n_way)
    queries = episode["query_x"]
    with ServeCluster(registry, replicas=2, max_batch=MAX_BATCH) as cluster:
        cluster.add_tenant("acme")
        with timed("cluster warmup (compile)", seconds):
            cluster.warmup(img=IMG)
        with timed(f"register {n_way} classes", seconds):
            for way in range(n_way):
                shots = episode["support_x"][episode["support_y"] == way]
                cluster.submit_register("acme", f"novel{way}",
                                        shots).result(120)
        with timed(f"classify one batch of {len(queries)}", seconds):
            res = cluster.submit_classify("acme", queries).result(120)

    store = registry.tenant_store("acme")
    means, ids = store.prototypes()
    head = store.head
    check(head.n_dev == len(devices) == 4, "head mesh spans 4 chips")
    placed = head.place(means)
    shard_devs = {s.device for s in placed.addressable_shards}
    rows = sorted(s.data.shape[0] for s in placed.addressable_shards)
    print(f"  prototype rows {means.shape} -> shards of {rows} rows on "
          f"{sorted(d.id for d in shard_devs)}")
    check(len(shard_devs) == 4 and rows == [n_way // 4] * 4,
          "prototype rows split across 4 devices")
    q = np.asarray(feats(queries))
    with timed("sharded vs serial head", seconds):
        sharded = head.sims(q, means)
        serial = ShardedNCMHead([devices[0]]).sims(q, means)
    print(f"  max |sharded - serial| {np.abs(sharded - serial).max():.3g}; "
          f"max |served - serial| {np.abs(res.sims - serial).max():.3g}")
    check(np.array_equal(sharded, serial),
          "sharded head == serial head on one chip, bitwise")
    check(res.class_ids == [ids[int(i)] for i in serial.argmax(axis=-1)],
          "served class ids == serial head argmax")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.runtime import use_compile_cache

    cache = use_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX finds "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache {cache}", flush=True)

    seconds: dict = {}
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            sharded_cluster_phase(args.seed, seconds)
        else:
            fsl_phases(args.seed, seconds)
            decode_phase(args.seed, seconds)
            kernel_phase(args.seed, seconds)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"phase seconds: {json.dumps(seconds)}")
    print(f"total seconds: {time.perf_counter() - t0:.3f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
