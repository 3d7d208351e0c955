"""End-to-end serving demo: the paper's real-time few-shot loop as a
running service (repro.serve, DESIGN.md §9).

Pretrains a quantized backbone on base classes, compiles BOTH deployment
artifacts (w6a4 int datapath + f32 reference), registers them in an
ArtifactRegistry, and drives a ServeEngine: novel classes register ONLINE
from support shots (no retraining, no retracing), queries classify against
the live prototype store, and the two bit-width artifacts serve A/B on the
same traffic.  Ends with the engine's latency/throughput report.

  PYTHONPATH=src python examples/serve_fsl.py [--steps 80] [--requests 200]

(The LM decode counterpart — same engine, different workload adapter —
is examples/serve_decode.py.)
"""

import argparse
import time

import numpy as np

from repro.core.quant import QuantConfig
from repro.data.synthetic import SyntheticImages
from repro.fsl.pipeline import FSLPipeline, pretrain_backbone
from repro.runtime import use_compile_cache
from repro.serve import ArtifactRegistry, ServeEngine

use_compile_cache()
ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=80)
ap.add_argument("--width", type=int, default=8)
ap.add_argument("--requests", type=int, default=200)
args = ap.parse_args()

data = SyntheticImages(n_base=16, n_novel=6, seed=0)
pipe = FSLPipeline(width=args.width, qcfg=QuantConfig.paper_w6a4())
print(f"== pretraining width-{args.width} backbone, {args.steps} steps ==")
out = pretrain_backbone(data, pipe, steps=args.steps, batch=32,
                        log_every=max(args.steps // 4, 1))

registry = ArtifactRegistry()
registry.register("w6a4-int", pipe.deploy(out["params"], datapath="int"),
                  default=True)
registry.register("f32-ref", pipe.deploy(out["params"], datapath="f32"))
dm = registry.get("w6a4-int").feats.deployed_model
print(f"artifacts: {registry.names()}, int weight storage "
      f"{dm.weight_bytes()} bytes")

rng = np.random.default_rng(1)
episode = data.episode(rng, n_way=5, k_shot=5, n_query=15)

with ServeEngine(registry, max_batch=32, batch_wait_ms=2.0) as eng:
    t0 = time.perf_counter()
    eng.warmup(img=data.img)
    print(f"warmup (all artifacts x all buckets): "
          f"{time.perf_counter() - t0:.1f}s — steady state never retraces")

    # novel classes go live from support shots, per artifact store
    for way in range(5):
        shots = episode["support_x"][episode["support_y"] == way]
        for art in registry.names():
            eng.submit_register(f"novel{way}", shots, artifact=art).result()
    print(f"registered 5 novel classes online "
          f"({registry.get('w6a4-int').store.counts()})")

    # A/B the two bit-width artifacts on the same query traffic
    for art in registry.names():
        futs = [eng.submit_classify(q[None], artifact=art, timeout=30.0)
                for q in episode["query_x"]]
        pred = [f.result(60).class_ids[0] for f in futs]
        acc = np.mean([p == f"novel{w}"
                       for p, w in zip(pred, episode["query_y"])])
        print(f"  {art}: {len(pred)} single-frame queries, "
              f"episode accuracy {acc * 100:.1f}%")

    # sustained mixed load through the default artifact
    frames = [episode["query_x"][i % len(episode["query_x"])][None]
              for i in range(args.requests)]
    t0 = time.perf_counter()
    futs = [eng.submit_classify(f, timeout=30.0) for f in frames]
    for f in futs:
        f.result(60)
    dt = time.perf_counter() - t0
    print(f"burst: {args.requests} requests in {dt:.2f}s "
          f"({args.requests / dt:.0f} req/s, dynamic batching)")
    print(eng.metrics.report())
    print(f"trace counts (flat == no retrace): {eng.trace_counts()}")
