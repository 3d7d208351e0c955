"""Roofline analysis from dry-run artifacts (EXPERIMENTS.md §Roofline).

Three terms per (arch × shape), single-pod mesh, TPU v5e constants:

  compute    = dot_FLOPs_per_device / peak_FLOP/s        (197 TF bf16/chip)
  memory     = HBM_bytes_per_device / HBM_bw             (819 GB/s/chip)
  collective = Σ_op payload_op · hops_op / link_bw       (50 GB/s/link ICI)

dot_FLOPs and collective payloads come from the trip-count-aware HLO parse
(launch/hlo_analysis.py); HBM bytes are modeled from the workload (weights +
activations + caches actually streamed per step — XLA's 'bytes accessed' is
pre-fusion and wildly overcounts, so we derive bytes from the memory
analysis of the compiled module: arguments touched once + temps).

Collective hop model (ring algorithms): all-reduce 2·(n-1)/n ≈ 2,
all-gather / reduce-scatter / all-to-all (n-1)/n ≈ 1, permute 1.

MODEL_FLOPS = 6·N·D (train), 2·N·D (prefill), 2·N_active·B (decode) — the
"useful" fraction = MODEL_FLOPS / HLO_dot_FLOPs catches remat, causal-chunk
waste and GSPMD padding.
"""

from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional

from repro.obs.costmodel import DEVICE_PEAKS

_V5E = DEVICE_PEAKS["TPU v5 lite"]
PEAK_FLOPS = _V5E["bf16_flops"]     # / chip
HBM_BW = _V5E["hbm_bw"]             # bytes/s / chip
LINK_BW = _V5E["ici_link_bw"]       # bytes/s / link (ICI)

ART_DIR = os.path.join(os.path.dirname(__file__), "artifacts", "dryrun")

_HOPS = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
         "all-to-all": 1.0, "collective-permute": 1.0}


def model_flops(rec: Dict) -> float:
    """Ideal per-device FLOPs: 6·N·D train, 2·N·D prefill, 2·N_active·B decode."""
    from repro.launch.specs import SHAPES
    sh = SHAPES[rec["shape"]]
    n_dev = rec["n_devices"]
    n_act = rec["n_active_params"]
    if rec["kind"] == "train":
        d = sh["batch"] * sh["seq"]
        return 6.0 * n_act * d / n_dev
    if rec["kind"] == "prefill":
        d = sh["batch"] * sh["seq"]
        return 2.0 * n_act * d / n_dev
    return 2.0 * n_act * sh["batch"] / n_dev


def memory_bytes(rec: Dict) -> float:
    """Per-device HBM traffic per step.

    Model: every live argument byte is streamed at least once (weights, opt
    state, caches — these dominate at our scales), plus temp buffer traffic
    (written+read ⇒ ×2).  Output bytes alias inputs (donation) and are
    already counted.  This is a *lower-bound-flavored* model, appropriate
    for a roofline.
    """
    mem = rec.get("memory_analysis", {})
    args = mem.get("argument_size_in_bytes", 0)
    temps = mem.get("temp_size_in_bytes", 0)
    return float(args + 2 * temps)


def collective_seconds(rec: Dict) -> float:
    total = 0.0
    for op, b in rec.get("collective_bytes_per_device", {}).items():
        total += _HOPS.get(op, 1.0) * float(b)
    return total / LINK_BW


def roofline(rec: Dict) -> Optional[Dict]:
    if rec.get("status") != "ok":
        return None
    t_c = rec["dot_flops_per_device"] / PEAK_FLOPS
    t_m = memory_bytes(rec) / HBM_BW
    t_x = collective_seconds(rec)
    mf = model_flops(rec)
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_x),
              key=lambda kv: kv[1])
    step = max(t_c, t_m, t_x)
    return {
        "arch": rec["arch"], "shape": rec["shape"], "variant": rec["variant"],
        "mesh": "2x16x16" if rec["multi_pod"] else "16x16",
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
        "dominant": dom[0],
        "step_s_lower_bound": step,
        "model_flops": mf,
        "hlo_dot_flops": rec["dot_flops_per_device"],
        "useful_fraction": mf / rec["dot_flops_per_device"]
        if rec["dot_flops_per_device"] else 0.0,
        "roofline_fraction": (mf / PEAK_FLOPS) / step if step else 0.0,
        "peak_gib": rec.get("memory_analysis", {}).get(
            "peak_memory_in_bytes", 0) / 2**30,
    }


def load_all(pattern: str = "*") -> List[Dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(ART_DIR, f"{pattern}.json"))):
        with open(path) as f:
            out.append(json.load(f))
    return out


def fmt_s(s: float) -> str:
    if s >= 1:
        return f"{s:7.2f}s "
    if s >= 1e-3:
        return f"{s*1e3:7.2f}ms"
    return f"{s*1e6:7.2f}us"


def main(variant: str = "base", mesh: str = "16x16"):
    rows = []
    skips = []
    for rec in load_all():
        if rec.get("variant", "base") != variant:
            continue
        want_mp = (mesh == "2x16x16")
        if rec.get("multi_pod") != want_mp:
            continue
        if rec.get("status") == "skipped":
            skips.append(rec)
            continue
        r = roofline(rec)
        if r:
            rows.append(r)
    rows.sort(key=lambda r: (r["arch"], r["shape"]))
    print(f"# Roofline ({mesh}, variant={variant}) — v5e: 197TF bf16, "
          f"819GB/s HBM, 50GB/s ICI")
    print(f"{'arch':18s} {'shape':12s} {'compute':9s} {'memory':9s} "
          f"{'coll':9s} {'dominant':10s} {'useful':7s} {'roofline%':9s} "
          f"{'peakGiB':8s}")
    for r in rows:
        print(f"{r['arch']:18s} {r['shape']:12s} {fmt_s(r['compute_s'])} "
              f"{fmt_s(r['memory_s'])} {fmt_s(r['collective_s'])} "
              f"{r['dominant']:10s} {r['useful_fraction']:6.2f}  "
              f"{100*r['roofline_fraction']:8.1f}% {r['peak_gib']:7.2f}")
    for s in skips:
        print(f"{s['arch']:18s} {s['shape']:12s} SKIPPED ({s['reason'][:60]})")
    return rows


if __name__ == "__main__":
    import sys
    main(*(sys.argv[1:] or []))
