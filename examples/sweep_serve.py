"""Sweep → serve the knee: the whole design environment as one story.

Runs the parallel, resumable DSE farm over a (W, A) grid (each point:
QAT-pretrain → compile both datapaths → bit-exactness probe → episode
accuracy / bytes / latency), publishes the Pareto-optimal points into a
live ArtifactRegistry — the registry default hot-swapped to the selected
knee — and serves classify traffic through the knee, A/B-ing every
frontier artifact on the same queries.

Run it TWICE to see the resume semantics: the second invocation completes
from the content-hash cache in milliseconds.

  PYTHONPATH=src python examples/sweep_serve.py [--steps 40] [--cache-dir .farm]
"""

import argparse
import time

import numpy as np

from repro.data.synthetic import SyntheticImages
from repro.explore import SweepFarm, publish_frontier, select_knee
from repro.runtime import use_compile_cache
from repro.serve import ArtifactRegistry, ServeEngine

use_compile_cache()
ap = argparse.ArgumentParser()
ap.add_argument("--cache-dir", default=".farm_cache")
ap.add_argument("--steps", type=int, default=40)
ap.add_argument("--width", type=int, default=8)
ap.add_argument("--grid", default="3x2,4x4,6x4,8x8",
                help="comma list of WxA points")
args = ap.parse_args()
grid = [tuple(int(b) for b in p.split("x")) for p in args.grid.split(",")]

print(f"== farming {len(grid)} grid points (cache: {args.cache_dir}) ==")
farm = SweepFarm(args.cache_dir, width=args.width, steps=args.steps,
                 episodes=5)
t0 = time.perf_counter()
result = farm.run(grid)
print(f"farm finished in {time.perf_counter() - t0:.1f}s: "
      f"{result.computed} computed, {result.hits} cache hits")
for i, rec in enumerate(result.points):
    mark = "*" if i in result.frontier else " "
    print(f" {mark} w{rec['w_bits']}a{rec['a_bits']}: "
          f"acc {rec['acc_mean']:.3f}±{rec['acc_ci95']:.3f}, "
          f"{rec['weight_bytes_int']} bytes, "
          f"{rec['int_ms_per_batch']:.2f} ms/batch, "
          f"bitexact={int(rec['bitexact_int_vs_f32'])}")

registry = ArtifactRegistry()
names = publish_frontier(result, registry)
knee = result.points[select_knee(result.points, result.frontier)]
print(f"published frontier: {names}; serving default = "
      f"w{knee['w_bits']}a{knee['a_bits']}-int "
      f"({knee['weight_bytes_int']} bytes)")

# serve a few episodes through the knee, A/B-ing every frontier artifact
data = SyntheticImages(n_base=farm.config["n_base"],
                       n_novel=farm.config["n_novel"],
                       seed=farm.config["seed"], img=farm.config["img"])
rng = np.random.default_rng(1)
ep = data.episode(rng, n_way=5, k_shot=5, n_query=15)

with ServeEngine(registry, max_batch=32, batch_wait_ms=2.0) as eng:
    eng.warmup(img=data.img)
    for way in range(5):
        shots = ep["support_x"][ep["support_y"] == way]
        for art in registry.names():
            eng.submit_register(f"novel{way}", shots, artifact=art).result(60)
    for art in registry.names():
        futs = [eng.submit_classify(q[None], artifact=art, timeout=30.0)
                for q in ep["query_x"]]
        pred = [f.result(60).class_ids[0] for f in futs]
        acc = np.mean([p == f"novel{w}"
                       for p, w in zip(pred, ep["query_y"])])
        meta = registry.metadata()[art]
        print(f"  {art}: served episode acc {acc * 100:.1f}% "
              f"({meta['weight_bytes']} bytes, "
              f"sweep acc {meta['acc_mean'] * 100:.1f}%)")
    print(eng.metrics.report())
