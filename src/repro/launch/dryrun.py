import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape) on the production
mesh, capture memory/cost analysis and the collective schedule.

MUST be the first jax-touching entry point in the process (the two lines
above run before any other import — jax locks device count on first init).

Usage:
  python -m repro.launch.dryrun --arch qwen3-14b --shape decode_32k
  python -m repro.launch.dryrun --all [--multi-pod both|single|multi]
  python -m repro.launch.dryrun --arch grok-1-314b --shape train_4k \
      --variant w8   # serving/step variants for the §Perf hillclimb

Artifacts: benchmarks/artifacts/dryrun/<arch>__<shape>__<mesh>__<variant>.json
"""

import argparse
import json
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist import act_sharding
from repro.dist.sharding import (
    set_fsdp_axes,
    set_moe_expert_axis,
    tree_batch_shardings,
    tree_cache_shardings,
    tree_opt_shardings,
    tree_param_shardings,
)
from repro.launch import specs as S
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.launch.steps import (
    make_decode_step,
    make_prefill_step,
    make_train_step,
    quantize_tree_for_serving,
)
from repro.launch import hlo_analysis
from repro.models.common import ArchConfig, get_config
from repro.optim import adamw_init

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "benchmarks", "artifacts", "dryrun")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "s4": 0.5, "u4": 0.5,
                "pred": 1, "c64": 8, "c128": 16}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(type_str: str) -> float:
    """'bf16[16,4096,384]{2,1,0}' -> bytes. Tuples handled by caller."""
    m = re.match(r"([a-z0-9]+)\[([0-9,]*)\]", type_str)
    if not m:
        return 0.0
    dt, dims = m.groups()
    if dt not in _DTYPE_BYTES:
        return 0.0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def parse_collectives(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Sum result sizes of every collective in the (SPMD-partitioned) HLO.

    Shapes in compiled.as_text() are per-device, so the sums are per-device
    payload bytes — exactly what the ICI roofline term wants."""
    out: Dict[str, Dict[str, float]] = {
        c: {"count": 0, "bytes": 0.0} for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"(?:%\S+\s*=\s*)?(\([^)]*\)|\S+\[[0-9,]*\]\S*)\s+"
                     r"([a-z0-9-]+)(?:-start)?\(", line)
        if not m:
            continue
        type_str, op = m.groups()
        if op.endswith("-start"):
            op = op[:-6]
        if op not in _COLLECTIVES:
            continue
        if type_str.startswith("("):
            total = sum(_shape_bytes(t.strip())
                        for t in type_str[1:-1].split(","))
        else:
            total = _shape_bytes(type_str)
        out[op]["count"] += 1
        out[op]["bytes"] += total
    return out


# ---------------------------------------------------------------------------
# Variants (hillclimb levers — each returns cfg overrides + context)
# ---------------------------------------------------------------------------
def apply_variant(cfg: ArchConfig, variant: str, mesh):
    """Returns (cfg, serving_bits, act_rules, notes)."""
    import dataclasses
    bspec = ("pod", "data") if "pod" in mesh.shape else "data"
    rules = {
        "residual": NamedSharding(mesh, P(bspec, None, None)),
        "logits": NamedSharding(mesh, P(bspec, None, "model")),
    }
    serving_bits = 0
    notes = []
    for v in (variant.split("+") if variant else []):
        if v in ("", "base"):
            continue
        elif v == "w8":
            serving_bits = 8
            notes.append("serving weights int8 (paper bit-width lever)")
        elif v == "w4":
            serving_bits = 4
            notes.append("serving weights int4-packed")
        elif v == "sp":
            rules["residual"] = NamedSharding(mesh, P(bspec, None, "model"))
            notes.append("sequence/feature-parallel residual stream")
        elif v == "seqsp":
            rules["residual"] = NamedSharding(mesh, P(bspec, "model", None))
            notes.append("sequence-parallel residual (seq on model axis)")
        elif v == "nologitsp":
            rules.pop("logits")
            notes.append("no logits sharding constraint")
        elif v == "noremat":
            cfg = dataclasses.replace(cfg, remat=False)
            notes.append("activation checkpointing off")
        elif v.startswith("accum"):
            cfg = dataclasses.replace(cfg, grad_accum=int(v[5:]))
            notes.append(f"grad_accum={v[5:]}")
        elif v.startswith("chunk"):
            cfg = dataclasses.replace(cfg, prefill_chunk=int(v[5:]))
            notes.append(f"prefill_chunk={v[5:]}")
        elif v.startswith("mesh"):
            notes.append(f"mesh re-factorized: {v[4:]}")
        elif v == "epmodel":
            notes.append("MoE experts sharded over the model axis "
                         "(EP on model; d_ff takes data)")
        elif v == "epdispatch":
            rules["moe_dispatch"] = NamedSharding(
                mesh, P("model", None, None))
            notes.append("MoE dispatch buffer expert-sharded on model")
        elif v == "epdispatchdata":
            rules["moe_dispatch"] = NamedSharding(
                mesh, P("data", None, None))
            notes.append("MoE dispatch buffer expert-home-sharded on data")
        elif v == "rematsave":
            cfg = dataclasses.replace(cfg, remat_policy="tp_outputs")
            notes.append("remat saves post-AR TP outputs "
                         "(backward re-runs no collectives)")
        elif v == "gradbf16":
            notes.append("bf16 gradient accumulation/reduction "
                         "(halves dW all-reduce payload)")
        elif v == "cachequant":
            notes.append("int8 KV cache")  # handled via cache dtype below
        elif v == "nofsdp":
            notes.append("FSDP off: pure TP + ZeRO-1 moments "
                         "(kills per-microbatch weight gathers)")
        elif v == "attnsp":
            rules["attn_chunk_q"] = NamedSharding(
                mesh, P(bspec, "model", None, None, None))
            rules["attn_q_rows"] = NamedSharding(
                mesh, P(bspec, "model", None, None))
            notes.append("attention q-rows sharded on model axis "
                         "(seq-TP: no sharded-contraction partial sums)")
        elif v == "headshard":
            rules["attn_heads"] = NamedSharding(
                mesh, P(bspec, None, "model", None))
            notes.append("attention head dim sharded on model "
                         "(GSPMD pads uneven head counts)")
        else:
            raise ValueError(f"unknown variant component '{v}'")
    return cfg, serving_bits, rules, notes


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------
def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               variant: str = "") -> Dict[str, Any]:
    cfg = get_config(arch)
    ok, why = S.cell_supported(cfg, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "variant": variant, "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    # mesh re-factorization lever: same 256 chips, different (data, model)
    # split — e.g. mesh32x8 makes TP=8 divide 40/8-head archs exactly.
    import re as _re
    mm = _re.search(r"mesh(\d+)x(\d+)", variant or "")
    if mm and not multi_pod:
        d_, m_ = int(mm.group(1)), int(mm.group(2))
        assert d_ * m_ == 256, "single-pod mesh must keep 256 chips"
        mesh = make_mesh((d_, m_), ("data", "model"))
    cfg, serving_bits, rules, notes = apply_variant(cfg, variant, mesh)
    kind = S.SHAPES[shape_name]["kind"]
    # >50B archs in multi-pod mode: FSDP widens across pods (ZeRO-3) —
    # pure-DP replicas of a 480B model cannot fit one pod's HBM.
    set_moe_expert_axis("model" if "epmodel" in (variant or "") else "data")
    if "nofsdp" in (variant or ""):
        set_fsdp_axes(())
    elif multi_pod and cfg.n_params() > 5e10:
        set_fsdp_axes(("pod", "data"))
        notes = notes + ["FSDP over (pod,data) — ZeRO-3 across pods"]
    else:
        set_fsdp_axes(("data",))
    t0 = time.time()

    with act_sharding.rules(rules):
        batch_sds = S.batch_specs(cfg, shape_name)
        batch_sh = tree_batch_shardings(batch_sds, mesh)

        if kind == "train":
            from repro.launch.steps import train_dtype_policy
            pdtype, moment_dtype, _ = train_dtype_policy(cfg)
            params_sds = S.param_specs(cfg, dtype=pdtype)
            params_sh = tree_param_shardings(params_sds, mesh)
            opt_sds = jax.eval_shape(
                lambda: adamw_init(params_sds, moment_dtype=moment_dtype))
            opt_sh = type(opt_sds)(
                step=NamedSharding(mesh, P()),
                m=tree_opt_shardings(params_sds, mesh),
                v=tree_opt_shardings(params_sds, mesh))
            import jax.numpy as _jnp
            step = make_train_step(
                cfg, compress_pod_grads=multi_pod,
                acc_shardings=tree_opt_shardings(params_sds, mesh),
                grad_dtype=_jnp.bfloat16 if "gradbf16" in (variant or "")
                else None)
            if multi_pod:
                res_sds = jax.eval_shape(
                    lambda: jax.tree.map(
                        lambda p: jnp.zeros(p.shape, pdtype), params_sds))
                res_sh = tree_opt_shardings(params_sds, mesh)
                fn = jax.jit(step,
                             in_shardings=(params_sh, opt_sh, batch_sh, res_sh),
                             out_shardings=(params_sh, opt_sh,
                                            NamedSharding(mesh, P()), res_sh),
                             donate_argnums=(0, 1, 3))
                lowered = fn.lower(params_sds, opt_sds, batch_sds, res_sds)
            else:
                fn = jax.jit(step,
                             in_shardings=(params_sh, opt_sh, batch_sh),
                             out_shardings=(params_sh, opt_sh,
                                            NamedSharding(mesh, P())),
                             donate_argnums=(0, 1))
                lowered = fn.lower(params_sds, opt_sds, batch_sds)

        elif kind == "prefill":
            params_sds = S.param_specs(cfg, serving_bits, dtype=jnp.bfloat16)
            params_sh = tree_param_shardings(params_sds, mesh)
            step = make_prefill_step(cfg)
            fn = jax.jit(step, in_shardings=(params_sh, batch_sh))
            lowered = fn.lower(params_sds, batch_sds)

        else:  # decode
            params_sds = S.param_specs(cfg, serving_bits, dtype=jnp.bfloat16)
            params_sh = tree_param_shardings(params_sds, mesh)
            cache_dtype = jnp.int8 if "cachequant" in (variant or "") \
                else jnp.bfloat16
            cache_sds = S.cache_specs(cfg, shape_name, dtype=cache_dtype)
            cache_sh = tree_cache_shardings(cache_sds, mesh)
            step = make_decode_step(cfg)
            fn = jax.jit(step,
                         in_shardings=(params_sh, batch_sh, cache_sh),
                         out_shardings=(NamedSharding(mesh, P()), cache_sh),
                         donate_argnums=(2,))
            lowered = fn.lower(params_sds, batch_sds, cache_sds)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    mem_d: Dict[str, Any] = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes", "peak_memory_in_bytes"):
        if mem is not None and hasattr(mem, attr):
            mem_d[attr] = int(getattr(mem, attr))

    hlo_text = compiled.as_text()
    colls = parse_collectives(hlo_text)
    deep = hlo_analysis.analyze(hlo_text)   # trip-count-aware (per device)

    n_dev = mesh.size
    result = {
        "arch": arch, "shape": shape_name, "variant": variant or "base",
        "multi_pod": multi_pod, "mesh": dict(mesh.shape),
        "status": "ok", "kind": kind,
        "n_devices": n_dev,
        "flops_once_through": float(cost.get("flops", 0.0)),
        "bytes_total": float(cost.get("bytes accessed", 0.0)),
        "dot_flops_per_device": float(deep["dot_flops"]),
        "collective_bytes_per_device": deep["collective_bytes"],
        "collective_counts": deep.get("collective_counts", {}),
        "memory_analysis": mem_d,
        "collectives_once_through": colls,
        "n_params": cfg.n_params(),
        "n_active_params": cfg.n_active_params(),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "notes": notes,
    }
    return result


def artifact_path(arch, shape, multi_pod, variant):
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    v = variant or "base"
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    return os.path.join(ARTIFACT_DIR, f"{arch}__{shape}__{mesh_tag}__{v}.json")


def run_cell(arch, shape, multi_pod, variant="", force=False) -> Dict:
    path = artifact_path(arch, shape, multi_pod, variant)
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    try:
        res = lower_cell(arch, shape, multi_pod, variant)
    except Exception as e:  # a failing cell is a bug — record it loudly
        res = {"arch": arch, "shape": shape, "multi_pod": multi_pod,
               "variant": variant or "base", "status": "FAILED",
               "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    with open(path, "w") as f:
        json.dump(res, f, indent=1)
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--variant", default="")
    ap.add_argument("--multi-pod", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    from repro.configs import ASSIGNED
    pods = {"single": [False], "multi": [True], "both": [False, True]}[
        args.multi_pod]

    cells = []
    if args.all:
        for arch in ASSIGNED:
            for shape in S.SHAPES:
                for mp in pods:
                    cells.append((arch, shape, mp))
    else:
        for mp in pods:
            cells.append((args.arch, args.shape, mp))

    n_ok = n_skip = n_fail = 0
    for arch, shape, mp in cells:
        res = run_cell(arch, shape, mp, args.variant, args.force)
        tag = f"{arch:18s} {shape:12s} {'2x16x16' if mp else '16x16':8s}"
        if res["status"] == "ok":
            n_ok += 1
            mem = res.get("memory_analysis", {})
            print(f"OK   {tag} dotflops={res['dot_flops_per_device']:.3e} "
                  f"lower={res['lower_s']}s compile={res['compile_s']}s "
                  f"args={mem.get('argument_size_in_bytes', 0)/2**30:.2f}GiB")
        elif res["status"] == "skipped":
            n_skip += 1
            print(f"SKIP {tag} ({res['reason'][:60]})")
        else:
            n_fail += 1
            print(f"FAIL {tag} {res['error'][:120]}")
    print(f"\n{n_ok} ok, {n_skip} skipped, {n_fail} FAILED")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
