"""Quantized LM decode serving through the ServeEngine (PR 10).

The second workload through the same compiler and the same serving
runtime: a tiny dense decoder LM is exported to the core Graph, lowered
onto the integer datapath, and served as greedy decode by the SAME
``ServeEngine`` that serves few-shot classify — admission, dynamic
batching, A/B artifact routing, metrics, and the zero-retrace discipline
all apply unchanged, because the workload specifics live in a
``DecodeAdapter``.

  PYTHONPATH=src python examples/serve_decode.py
  PYTHONPATH=src python examples/serve_decode.py --tokens 24 --prompts 8

``legacy_main`` is the former ``repro.launch.serve`` demo (eager bf16
decode loop with optionally bit-width-reduced weights), kept verbatim so
the deprecated ``repro.launch.serve.main`` entry point still behaves
identically:

  PYTHONPATH=src python examples/serve_decode.py --legacy --reduced --bits 8
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np


# -- the engine-based decode-serving demo ------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="lm-tiny")
    ap.add_argument("--prompts", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=5)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--capacities", default="16,32")
    ap.add_argument("--legacy", action="store_true",
                    help="run the pre-PR-10 eager decode-loop demo instead")
    args, rest = ap.parse_known_args(argv)
    from repro.runtime import use_compile_cache

    use_compile_cache()
    if args.legacy:
        return legacy_main(rest)

    import repro.configs.lm_tiny  # noqa: F401  (registers the arch)
    from repro.models import lm
    from repro.models.common import get_config
    from repro.serve import ArtifactRegistry, ServeEngine
    from repro.serve.decode import (
        DecodeAdapter,
        build_decode_artifact,
        greedy_generate,
    )

    cfg = get_config(args.arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    caps = tuple(int(c) for c in args.capacities.split(","))

    print(f"== compiling {args.arch} decode graph (int + f32 datapaths) ==")
    art_int = build_decode_artifact(params, cfg, datapath="int",
                                    capacities=caps)
    art_f32 = build_decode_artifact(params, cfg, datapath="f32",
                                    capacities=caps)
    print(f"weight bytes: int {art_int.weight_bytes()} vs "
          f"f32 {art_f32.weight_bytes()}")

    reg = ArtifactRegistry()
    adapter = DecodeAdapter()
    reg.register("lm-int", art_int, adapter=adapter, default=True)
    reg.register("lm-f32", art_f32, adapter=adapter)
    eng = ServeEngine(reg, max_batch=8, buckets=(1, 2, 4, 8))
    base = eng.warmup()
    print(f"post-warmup trace counts: {base}")

    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, cfg.vocab, args.prompt_len))
               for _ in range(args.prompts)]
    t0 = time.perf_counter()
    out_int = greedy_generate(eng, prompts, args.tokens)
    dt = time.perf_counter() - t0
    n_tok = args.prompts * args.tokens
    print(f"int decode: {n_tok} tokens in {dt*1e3:.0f} ms "
          f"({n_tok/dt:.1f} tok/s through the engine)")
    print("sample:", out_int[0][:12])

    out_f32 = greedy_generate(eng, prompts, args.tokens, artifact="lm-f32")
    print("int == f32 greedy tokens:", out_int == out_f32)

    after = eng.trace_counts()
    print("retraces under load:",
          {k: after[k] - base[k] for k in after})
    print(eng.metrics.report())
    eng.stop()
    return out_int


# -- the former repro.launch.serve demo (verbatim) ---------------------------

def legacy_main(argv=None):
    """Prefill + batched greedy decode with (optionally) bit-width-reduced
    weights — the eager big-transformer loop that predates the compiled
    decode path above."""
    import jax.numpy as jnp

    from repro.launch.steps import (
        make_decode_step,
        model_module,
        quantize_tree_for_serving,
    )
    from repro.models.common import get_config

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--bits", type=int, default=0, choices=[0, 4, 8],
                    help="serving weight bit-width (0 = bf16)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--tokens", type=int, default=16)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        from repro.models.testing import reduce_config
        cfg = reduce_config(cfg)
    mod = model_module(cfg)

    params = mod.init_params(jax.random.PRNGKey(0), cfg)
    if args.bits:
        params = quantize_tree_for_serving(params, args.bits)
        print(f"serving at w{args.bits} "
              f"({'packed int4' if args.bits == 4 else 'int8'} weights)")

    B = args.batch
    max_len = args.prompt_len + args.tokens + 1
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (B, args.prompt_len)),
                         jnp.int32)
    cache = mod.init_cache(cfg, B, max_len,
                           dtype=jnp.dtype(cfg.compute_dtype))

    decode = jax.jit(make_decode_step(cfg))

    # prefill by stepping the prompt through the cache (small-model path;
    # production uses the fused prefill + cache write)
    tok = prompt[:, :1]
    for t in range(args.prompt_len):
        tok, cache = decode(params, {"tokens": prompt[:, t:t + 1]}, cache)
        tok = tok[:, None]

    out = []
    t0 = time.time()
    for _ in range(args.tokens):
        tok, cache = decode(params, {"tokens": tok}, cache)
        tok = tok[:, None]
        out.append(tok)
    jax.block_until_ready(tok)
    dt = time.time() - t0
    gen = jnp.concatenate(out, axis=1)
    print(f"generated {args.tokens} tokens x {B} seqs in {dt*1e3:.0f} ms "
          f"({B*args.tokens/dt:.1f} tok/s)")
    print("sample:", np.asarray(gen[0][:12]))
    return gen


if __name__ == "__main__":
    main()
