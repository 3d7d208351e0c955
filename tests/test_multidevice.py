"""Multi-device tests, run in subprocesses so XLA_FLAGS device-count hacking
never leaks into the main test process (smoke tests must see 1 device)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_pipeline_parallel_matches_sequential():
    """GPipe pipeline over 4 stages == sequential apply, fwd AND grad."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.dist.pipeline import pipeline_apply
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("pipe",))
        n_stages, n_micro, mb, d = 4, 8, 2, 16
        key = jax.random.PRNGKey(0)
        ws = jax.random.normal(key, (n_stages, d, d)) * 0.3

        def stage_fn(w, x):
            return jnp.tanh(x @ w)

        x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))

        def pipelined(ws, x):
            return pipeline_apply(stage_fn, ws, x, mesh)

        def sequential(ws, x):
            y = x
            for i in range(n_stages):
                y = stage_fn(ws[i], y)
            return y

        got = jax.jit(pipelined)(ws, x)
        want = sequential(ws, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

        g1 = jax.grad(lambda w: jnp.sum(pipelined(w, x) ** 2))(ws)
        g2 = jax.grad(lambda w: jnp.sum(sequential(w, x) ** 2))(ws)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-4, atol=1e-4)
        print("PIPELINE_OK")
    """)
    assert "PIPELINE_OK" in out


def test_sharded_train_step_runs_and_matches_single_device():
    """The real make_train_step on a 2x2 debug mesh: executes, loss finite,
    and equals the unsharded single-device result (SPMD correctness)."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models.common import get_config
        from repro.models.testing import reduce_config
        from repro.models import lm
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import make_train_step
        from repro.dist.sharding import (tree_param_shardings,
            tree_batch_shardings, tree_opt_shardings)
        from repro.optim import adamw_init
        import dataclasses

        cfg = reduce_config(get_config("qwen2.5-3b"), grad_accum=2)
        mesh = make_mesh((2, 2), ("data", "model"))
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        opt = adamw_init(params)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 4, 16), 0, cfg.vocab)
        batch = {"tokens": toks, "labels": jnp.roll(toks, -1, -1)}
        step = make_train_step(cfg)

        # single device reference
        p1, o1, loss1 = jax.jit(step)(params, opt, batch)

        psh = tree_param_shardings(params, mesh)
        osh = type(opt)(step=NamedSharding(mesh, P()),
                        m=tree_opt_shardings(params, mesh),
                        v=tree_opt_shardings(params, mesh))
        bsh = tree_batch_shardings(batch, mesh)
        p_s = jax.device_put(params, psh)
        o_s = jax.device_put(opt, osh)
        b_s = jax.device_put(batch, bsh)
        p2, o2, loss2 = jax.jit(step, in_shardings=(psh, osh, bsh),
                                out_shardings=(psh, osh, NamedSharding(mesh, P())))(
            p_s, o_s, b_s)
        assert np.isfinite(float(loss2))
        np.testing.assert_allclose(float(loss1), float(loss2), rtol=2e-4)
        # NOTE: Adam's first step is lr*sign(g)-like, so per-entry param
        # equality is ill-posed under cross-sharding reduction-order noise
        # (any near-zero grad flips its sign bit).  The well-posed SPMD
        # check: the LOSS LANDSCAPE position after the update must agree.
        mb = jax.tree.map(lambda x: x[0], batch)
        after1 = float(lm.loss_fn(p1, mb, cfg))
        after2 = float(lm.loss_fn(jax.device_put(p2, psh), mb, cfg))
        np.testing.assert_allclose(after1, after2, rtol=5e-3)
        print("SHARDED_TRAIN_OK", float(loss2), after1, after2)
    """)
    assert "SHARDED_TRAIN_OK" in out


def test_sharded_decode_runs():
    """Decode step with sharded KV cache on a 2x2 mesh."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models.common import get_config
        from repro.models.testing import reduce_config
        from repro.models import lm
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import make_decode_step
        from repro.dist.sharding import (tree_param_shardings,
            tree_batch_shardings, tree_cache_shardings)

        cfg = reduce_config(get_config("qwen3-14b"), compute_dtype="float32")
        mesh = make_mesh((2, 2), ("data", "model"))
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        cache = lm.init_cache(cfg, B=4, max_len=32, dtype=jnp.float32)
        batch = {"tokens": jnp.zeros((4, 1), jnp.int32)}
        step = make_decode_step(cfg)
        psh = tree_param_shardings(params, mesh)
        csh = tree_cache_shardings(cache, mesh)
        bsh = tree_batch_shardings(batch, mesh)
        fn = jax.jit(step, in_shardings=(psh, bsh, csh),
                     out_shardings=(NamedSharding(mesh, P()), csh))
        tok, cache2 = fn(jax.device_put(params, psh),
                         jax.device_put(batch, bsh),
                         jax.device_put(cache, csh))
        assert tok.shape == (4,)
        for leaf in jax.tree.leaves(cache2):
            assert bool(jnp.isfinite(leaf.astype(jnp.float32)).all())
        # the per-layer cache lengths advanced
        assert int(cache2["attn"]["len"].min()) == 1
        print("SHARDED_DECODE_OK")
    """)
    assert "SHARDED_DECODE_OK" in out


def test_mini_dryrun_8dev():
    """End-to-end dryrun machinery on an 8-device debug mesh: lower, compile,
    trip-count-aware analysis, collective extraction."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models.common import get_config
        from repro.models.testing import reduce_config
        from repro.models import lm
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import make_train_step
        from repro.launch import hlo_analysis
        from repro.dist.sharding import (tree_param_shardings,
            tree_batch_shardings, tree_opt_shardings)
        from repro.optim import adamw_init

        cfg = reduce_config(get_config("grok-1-314b"), grad_accum=2,
                            moe_capacity_factor=1.25)
        mesh = make_mesh((4, 2), ("data", "model"))
        params_sds = jax.eval_shape(
            lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
        psh = tree_param_shardings(params_sds, mesh)
        opt_sds = jax.eval_shape(lambda: adamw_init(params_sds))
        osh = type(opt_sds)(step=NamedSharding(mesh, P()),
                            m=tree_opt_shardings(params_sds, mesh),
                            v=tree_opt_shardings(params_sds, mesh))
        batch_sds = {"tokens": jax.ShapeDtypeStruct((2, 4, 16), jnp.int32),
                     "labels": jax.ShapeDtypeStruct((2, 4, 16), jnp.int32)}
        bsh = tree_batch_shardings(batch_sds, mesh)
        step = make_train_step(cfg)
        lowered = jax.jit(step, in_shardings=(psh, osh, bsh),
                          out_shardings=(psh, osh, NamedSharding(mesh, P()))
                          ).lower(params_sds, opt_sds, batch_sds)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes > 0
        res = hlo_analysis.analyze(compiled.as_text())
        assert res["dot_flops"] > 0, "analyzer found no dots"
        total_coll = sum(res["collective_bytes"].values())
        assert total_coll > 0, "sharded MoE train must communicate"
        print("MINI_DRYRUN_OK", res["dot_flops"], total_coll)
    """)
    assert "MINI_DRYRUN_OK" in out
