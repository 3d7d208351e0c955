"""Training launcher: data pipeline → sharded train_step → checkpoint/restart
→ straggler policy.  Runs reduced configs end-to-end on CPU (the e2e example)
and is the entry point a real multi-host deployment would `python -m`.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --reduced \
      --steps 30 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.ckpt import CheckpointManager
from repro.data.synthetic import token_lm_batch
from repro.dist.sharding import (
    tree_batch_shardings,
    tree_opt_shardings,
    tree_param_shardings,
)
from repro.dist.straggler import StragglerMonitor
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_train_step, model_module
from repro.models.common import get_config
from repro.optim import adamw_init


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        from repro.models.testing import reduce_config
        cfg = reduce_config(cfg, grad_accum=2)
    mod = model_module(cfg)

    n_dev = len(jax.devices())
    mesh = make_mesh((max(n_dev // 2, 1), min(n_dev, 2)),
                     ("data", "model")) if n_dev > 1 else \
        make_mesh((1, 1), ("data", "model"))

    params = mod.init_params(jax.random.PRNGKey(0), cfg)
    opt = adamw_init(params)
    start_step = 0
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    if mgr and args.resume and mgr.latest_step() is not None:
        state = mgr.restore({"params": params, "m": opt.m, "v": opt.v})
        params = state["params"]
        opt = type(opt)(step=jnp.asarray(mgr.meta()["step"], jnp.int32),
                        m=state["m"], v=state["v"])
        start_step = mgr.meta()["step"]
        print(f"resumed from step {start_step}")

    psh = tree_param_shardings(params, mesh)
    osh = type(opt)(step=NamedSharding(mesh, P()),
                    m=tree_opt_shardings(params, mesh),
                    v=tree_opt_shardings(params, mesh))
    step_fn = make_train_step(cfg, lr=3e-4)
    monitor = StragglerMonitor()

    def make_batch(i):
        b = token_lm_batch(i, args.batch, args.seq, cfg.vocab)
        n_micro = cfg.grad_accum
        return {k: jnp.asarray(v).reshape(n_micro, args.batch // n_micro, -1)
                for k, v in b.items()}

    bsh = tree_batch_shardings(make_batch(0), mesh)
    jit_step = jax.jit(step_fn, in_shardings=(psh, osh, bsh),
                       out_shardings=(psh, osh, NamedSharding(mesh, P())))
    params = jax.device_put(params, psh)
    opt = jax.device_put(opt, osh)

    for i in range(start_step, start_step + args.steps):
        t0 = time.time()
        batch = jax.device_put(make_batch(i), bsh)
        params, opt, loss = jit_step(params, opt, batch)
        dt = time.time() - t0
        verdict = monitor.observe(i, dt)
        if verdict == "evict":
            # policy: checkpoint, shrink mesh, resume (elastic path). In a
            # single process we checkpoint + log; a cluster agent restarts.
            if mgr:
                mgr.save(i, {"params": jax.device_get(params),
                             "m": jax.device_get(opt.m),
                             "v": jax.device_get(opt.v)},
                         meta={"step": i, "reason": "straggler-evict"})
            print(f"step {i}: straggler evict policy fired")
        if i % 5 == 0 or i == start_step + args.steps - 1:
            print(f"step {i:4d} loss {float(loss):.4f} ({dt*1e3:.0f} ms)")
        if mgr and (i + 1) % args.ckpt_every == 0:
            mgr.save(i + 1, {"params": jax.device_get(params),
                             "m": jax.device_get(opt.m),
                             "v": jax.device_get(opt.v)},
                     meta={"step": i + 1, "mesh": list(mesh.shape.values()),
                           "arch": cfg.name})
    return float(loss)


if __name__ == "__main__":
    main()
