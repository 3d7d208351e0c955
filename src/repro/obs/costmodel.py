"""Per-node cost attribution for a :class:`DeployedModel`.

``profile_deployed(dm, example)`` walks the deployed HW graph with shapes
inferred for the given batch and produces one row per node:

* **flops** — analytic op count (matmul-family: ``2·|out|·K``; threshold
  ops: ``|out|·L`` compares against an L-level table; pools/elementwise:
  ``|out|``; pure data movement: 0);
* **bytes** — tensor traffic: inputs + outputs at their *storage* width
  (``graph.dtypes`` FixedPointSpec bits when annotated — packed int4 counts
  at 0.5 B/elem — else f32), initializers at their actual ``nbytes``;
* **est_ms** — single-node roofline bound, ``max(flops/peak, bytes/bw)``,
  with the peaks of the device kind it runs on from :data:`DEVICE_PEAKS`
  (int8 peak for int8 matmuls, bf16 peak otherwise; the CPU row is
  deliberately coarse — the *ranking* is what the farm consumes, not the
  absolute value);
* **kernel** — the dispatch label from
  :meth:`DeployedModel.dispatch_table`, so a node whose cost model says
  "cheap" but whose kernel says ``ref-oracle`` is visible in one row.

Totals include an optional **xla** section from
``jax.stages.Compiled.cost_analysis()`` on the same batch shape — XLA's own
flops/bytes for the whole program, a cross-check on the analytic model.
The farm records ``totals.est_ms`` as ``modeled_ms`` per sweep point so the
Pareto frontier can rank by modeled hardware latency, not just bytes.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["DEVICE_PEAKS", "device_peaks", "profile_deployed",
           "render_profile"]

# Peak rates per device, keyed by ``jax.Device.device_kind`` — the one
# table the cost model and ``benchmarks/roofline.py`` read.  "TPU v5 lite"
# (TPU v5e): Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
# 393 TOP/s int8, 819 GB/s HBM, 1,600 Gbit/s of interconnect over 4 links.
# "cpu": a generic server-core ballpark that only ranks CPU-backend
# profiles; it is never a device number.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bw": 819e9, "ici_link_bw": 50e9},
    "cpu": {"bf16_flops": 1e11, "int8_ops": 1e11, "hbm_bw": 2e10,
            "ici_link_bw": 0.0},
}


def device_peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of ``device_kind``.  A device missing from
    :data:`DEVICE_PEAKS` is an error, never a fallback to another row."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; add "
                       f"its published peaks to DEVICE_PEAKS") from None


_MATMUL_OPS = {"matmul", "matmul_int", "mvau", "mvau_int"}
_THRESHOLD_OPS = {"multithreshold", "multithreshold_int"}
_ELEMENTWISE_OPS = {"add", "mul", "quantize", "dequantize", "requantize",
                    "maxpool", "global_acc_pool"}
_MOVEMENT_OPS = {"im2col", "transpose", "flatten", "reshape"}


def _numel(shape) -> float:
    n = 1.0
    for d in shape:
        n *= int(d)
    return n


def _elt_bytes(g, tensor: str) -> float:
    """Storage bytes per element: annotated fixed-point width when the
    datatype pass ran, f32 otherwise."""
    spec = g.dtypes.get(tensor)
    if spec is not None and getattr(spec, "total_bits", None):
        return spec.total_bits / 8.0
    return 4.0


def _tensor_bytes(g, tensor: str) -> float:
    if tensor in g.initializers:
        return float(np.asarray(g.initializers[tensor]).nbytes)
    shape = g.shapes.get(tensor)
    if shape is None:
        return 0.0
    return _numel(shape) * _elt_bytes(g, tensor)


def _node_flops(g, node) -> float:
    out_shape = g.shapes.get(node.outputs[0])
    if out_shape is None:
        return 0.0
    out_n = _numel(out_shape)
    if node.op in _MATMUL_OPS:
        in_shape = g.shapes.get(node.inputs[0])
        k = int(in_shape[-1]) if in_shape else 1
        return 2.0 * out_n * k
    if node.op in _THRESHOLD_OPS:
        # compare-count datapath: every output element compares against the
        # full L-level threshold table
        t = node.inputs[-1]
        tshape = (g.shapes.get(t)
                  or np.shape(g.initializers.get(t, ())))
        levels = int(tshape[-1]) if tshape else 1
        return out_n * max(levels, 1)
    if node.op == "maxpool":
        k = int(node.attrs.get("kernel", 2))
        return out_n * k * k
    if node.op == "global_acc_pool":
        in_shape = g.shapes.get(node.inputs[0])
        return _numel(in_shape) if in_shape else out_n
    if node.op in _ELEMENTWISE_OPS:
        return out_n
    return 0.0  # movement / unknown: bandwidth-bound by construction


def _xla_totals(dm, x) -> Optional[Dict[str, float]]:
    """Whole-program flops/bytes from XLA's own cost analysis (AOT lower +
    compile on the profile shape).  Best-effort: absent backends or API
    drift degrade to None, never to a crash."""
    try:
        ca = dm._jitted.lower(x).compile().cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    out = {}
    for key, name in (("flops", "flops"),
                      ("bytes accessed", "bytes_accessed")):
        v = ca.get(key)
        if v is not None:
            out[name] = float(v)
    return out or None


def profile_deployed(dm, example, *, xla: bool = True,
                     device_kind: Optional[str] = None) -> Dict[str, Any]:
    """Per-node FLOPs/bytes/estimated-ms table for one batch shape.

    ``example`` is a batched input (same contract as ``dm(example)``).
    Returns ``{"batch", "device_kind", "nodes": [row...], "totals",
    "xla"}``; rows carry ``share`` of total modeled time so the table reads
    as an attribution, and ``kernel`` from the live dispatch table.
    """
    x = jnp.asarray(example)
    kind = device_kind or jax.devices()[0].device_kind
    peaks = device_peaks(kind)
    bw = peaks["hbm_bw"]

    g = dm.graph.copy()
    if len(dm.input_names) != 1:
        raise ValueError("profile_deployed supports single-input graphs")
    g.infer_shapes({dm.input_names[0]: x})
    kernels = {r["tensor"]: r["kernel"] for r in dm.dispatch_table()}

    rows = []
    for node in g.nodes:
        flops = _node_flops(g, node)
        peak = (peaks["int8_ops"] if node.op in _MATMUL_OPS
                and node.attrs.get("int8_ok") else peaks["bf16_flops"])
        nbytes = (sum(_tensor_bytes(g, t) for t in node.inputs)
                  + sum(_tensor_bytes(g, t) for t in node.outputs))
        est_ms = max(flops / peak, nbytes / bw) * 1e3
        rows.append({
            "tensor": node.outputs[0], "op": node.op,
            "kernel": kernels.get(node.outputs[0], "?"),
            "flops": flops, "bytes": nbytes, "est_ms": est_ms,
            "bound": ("compute" if flops / peak >= nbytes / bw
                      else "memory"),
        })

    total_ms = sum(r["est_ms"] for r in rows) or 1.0
    for r in rows:
        r["share"] = r["est_ms"] / total_ms
    totals = {
        "flops": sum(r["flops"] for r in rows),
        "bytes": sum(r["bytes"] for r in rows),
        "est_ms": sum(r["est_ms"] for r in rows),
    }
    return {
        "batch": int(x.shape[0]) if x.ndim else 1,
        "device_kind": kind,
        "nodes": rows,
        "totals": totals,
        "xla": _xla_totals(dm, x) if xla else None,
    }


def render_profile(prof: Dict[str, Any], top: int = 0) -> str:
    """Human-readable attribution table (sorted by modeled share)."""
    rows = sorted(prof["nodes"], key=lambda r: -r["est_ms"])
    if top:
        rows = rows[:top]
    lines = [f"profile: batch={prof['batch']} "
             f"device_kind={prof['device_kind']} "
             f"modeled {prof['totals']['est_ms']*1e3:.1f} us "
             f"({prof['totals']['flops']/1e6:.2f} MFLOP, "
             f"{prof['totals']['bytes']/1e6:.3f} MB)"]
    for r in rows:
        lines.append(
            f"  {r['share']*100:5.1f}%  {r['est_ms']*1e3:8.2f} us  "
            f"{r['flops']/1e6:9.3f} MF {r['bytes']/1e3:9.1f} kB "
            f"[{r['bound'][:3]}] {r['op']:18s} {r['kernel']:12s} "
            f"{r['tensor']}")
    xla = prof.get("xla")
    if xla:
        f = xla.get("flops")
        b = xla.get("bytes_accessed")
        lines.append("  xla cost_analysis: "
                     + ", ".join(filter(None, [
                         f"{f/1e6:.2f} MFLOP" if f is not None else None,
                         f"{b/1e6:.3f} MB accessed"
                         if b is not None else None])))
    return "\n".join(lines)
