"""Multi-tenant sharded serving cluster demo (repro.serve.cluster,
DESIGN.md §10).

Pretrains a quantized backbone, registers it as the shared default in a
TenantRegistry, and drives a two-replica ServeCluster through a persistent
compile cache:

* tenants onboard online with private prototype namespaces — "acme"'s
  classes are invisible to "bobcorp" even though both serve from the SAME
  compiled executables;
* per-tenant quotas shed a flooding tenant with ``TenantOverQuota`` while
  well-behaved tenants keep serving;
* the compile cache is then replayed into a brand-new replica: warmup is a
  deserialize, not a compile, and its trace count stays zero.

  PYTHONPATH=src python examples/serve_cluster.py [--steps 80] [--width 8]
"""

import argparse
import tempfile
import time

import numpy as np

from repro.ckpt import CompileCache
from repro.core.quant import QuantConfig
from repro.data.synthetic import SyntheticImages
from repro.fsl.pipeline import FSLPipeline, pretrain_backbone
from repro.runtime import use_compile_cache
from repro.serve.cluster import ServeCluster, TenantOverQuota, TenantRegistry

use_compile_cache()
ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=80)
ap.add_argument("--width", type=int, default=8)
ap.add_argument("--cache-dir", default=None,
                help="compile cache dir (default: fresh temp dir)")
args = ap.parse_args()

data = SyntheticImages(n_base=16, n_novel=6, seed=0)
pipe = FSLPipeline(width=args.width, qcfg=QuantConfig.paper_w6a4())
print(f"== pretraining width-{args.width} backbone, {args.steps} steps ==")
out = pretrain_backbone(data, pipe, steps=args.steps, batch=32,
                        log_every=max(args.steps // 4, 1))

registry = TenantRegistry()
registry.register_backbone("w6a4-int",
                           pipe.deploy(out["params"], datapath="int"),
                           default=True)
cache = CompileCache(args.cache_dir or tempfile.mkdtemp(prefix="repro-aot-"))

rng = np.random.default_rng(1)
episode = data.episode(rng, n_way=5, k_shot=5, n_query=15)

with ServeCluster(registry, replicas=2, max_batch=32, batch_wait_ms=2.0,
                  tenant_quota=0.25, compile_cache=cache) as cluster:
    for tenant in ("acme", "bobcorp"):
        cluster.add_tenant(tenant)
    t0 = time.perf_counter()
    cluster.warmup(img=data.img)
    print(f"cold warmup (compile + publish to cache): "
          f"{time.perf_counter() - t0:.1f}s, "
          f"{cache.stores} executables cached")

    # each tenant registers its own classes — private namespaces over the
    # shared backbone
    for way in range(5):
        shots = episode["support_x"][episode["support_y"] == way]
        cluster.submit_register("acme", f"novel{way}", shots).result(60)
    cluster.submit_register(
        "bobcorp", "other",
        episode["support_x"][episode["support_y"] == 0]).result(60)
    print(f"acme classes:    {registry.tenant_store('acme').counts()}")
    print(f"bobcorp classes: {registry.tenant_store('bobcorp').counts()}")

    # same query traffic, tenant-isolated answers; in-flight stays bounded —
    # a tenant's capacity is its HOME replica's quota, not the cluster sum
    futs, pred = [], []
    for q in episode["query_x"]:
        futs.append(cluster.submit_classify("acme", q[None], timeout=30.0))
        if len(futs) >= 32:
            pred.extend(f.result(60).class_ids[0] for f in futs)
            futs.clear()
    pred.extend(f.result(60).class_ids[0] for f in futs)
    acc = np.mean([p == f"novel{w}"
                   for p, w in zip(pred, episode["query_y"])])
    print(f"acme: {len(pred)} queries, episode accuracy {acc * 100:.1f}%")

    # a flooding tenant hits ITS quota (TenantOverQuota), never the shared
    # queue — bobcorp keeps serving untouched
    frame = episode["query_x"][0][None]
    flood, over_quota = [], 0
    for _ in range(200):
        try:
            flood.append(cluster.submit_classify("acme", frame))
        except TenantOverQuota:
            over_quota += 1
    for f in flood:
        f.result(60)
    bob = cluster.submit_classify("bobcorp", frame).result(60)
    print(f"flood: {len(flood)} admitted, {over_quota} quota-rejected; "
          f"bobcorp still serving ({bob.class_ids[0]!r})")

    # a new replica warms instantly: the shared artifacts already hold every
    # bucket executable in-process.  A RESTARTED process restores them from
    # the compile cache instead — serve_bench.py --cluster times that path.
    t0 = time.perf_counter()
    cluster.add_replica()
    print(f"add_replica warm start: {time.perf_counter() - t0:.2f}s "
          f"(zero compiles; cache stores {cache.stores})")
    snap = cluster.metrics_snapshot()
    print(f"completed {snap['completed']:.0f}, over_quota "
          f"{snap['over_quota']:.0f}, per-tenant "
          f"{ {t: int(s['completed']) for t, s in snap['tenants'].items()} }")
    print(f"trace counts (flat == no retrace): {cluster.trace_counts()}")
