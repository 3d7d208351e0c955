"""Benchmark entry point — one function per paper table/figure.

Prints ``name,metric,value`` CSV lines. ``--quick`` trims iteration counts
(used by the test suite); full runs reproduce EXPERIMENTS.md §Paper-validation.

The compile benchmark additionally serializes to ``BENCH_pr2.json`` at the
repo root (interpreter vs f32 artifact vs int artifact latency, weight
bytes per bit-width config), the serve benchmark to ``BENCH_pr3.json``
(single-request vs dynamically-batched serving throughput), the farm
benchmark to ``BENCH_pr4.json`` (per-point sweep wall-clock, speedup vs
serial, resume speedup), and the cluster benchmark to ``BENCH_pr6.json``
(cold start vs compile-cache restore, overload tail latency, noisy-neighbor
isolation), and the fused-datapath benchmark to ``BENCH_pr7.json`` (fused
int artifact vs f32 vs unfused int at b1/b16, serve-side rps rows, interior
quantize/dequantize census), and the per-layer search benchmark to
``BENCH_pr9.json`` (best searched mixed-precision plan vs best uniform grid
point on the acc/bytes frontier, bit-exact registry serve of the searched
artifact), and the decode
benchmark to ``BENCH_pr10.json`` (int vs f32 LM decode-step latency at
b1/b16, engine greedy tokens/s, zero-retrace and bitwise-vs-eager gates)
— the machine-readable perf trajectory successive PRs diff against.
"""

from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="",
                    help="comma list: table2,table3,fig5,roofline,compile,"
                         "serve,cluster,farm,pr7,pr9,pr10")
    ap.add_argument("--bench-json", default=None,
                    help="where the compile benchmark dict is written "
                         "(default: repo-root BENCH_pr2.json for full runs; "
                         "--quick runs go to the system temp dir so they "
                         "never clobber the committed trajectory file)")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else set()

    def want(name):
        return not only or name in only

    t0 = time.time()
    if want("table2"):
        from benchmarks import table2_accuracy
        table2_accuracy.run(quick=args.quick)
    if want("table3"):
        from benchmarks import table3_throughput
        table3_throughput.run(quick=args.quick)
    if want("fig5"):
        from benchmarks import fig5_pipeline
        fig5_pipeline.run(quick=args.quick)
    if want("compile"):
        import jax

        from benchmarks import compile_bench
        results = compile_bench.run(quick=args.quick)
        path = args.bench_json
        if path is None:
            path = (os.path.join(tempfile.gettempdir(), "BENCH_pr2.quick.json")
                    if args.quick
                    else os.path.join(_REPO_ROOT, "BENCH_pr2.json"))
        payload = {"benchmark": "compile", "quick": bool(args.quick),
                   "backend": jax.default_backend(), "metrics": results}
        with open(path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"compile,bench_json,{path}")
    if want("serve"):
        from benchmarks import serve_bench
        serve_bench.write_json(serve_bench.run(quick=args.quick),
                               quick=args.quick)
    if want("cluster"):
        from benchmarks import serve_bench
        serve_bench.write_cluster_json(
            serve_bench.run_cluster(quick=args.quick), quick=args.quick)
    if want("farm"):
        from benchmarks import farm_bench
        farm_bench.write_json(farm_bench.run(quick=args.quick),
                              quick=args.quick)
    if want("pr7"):
        from benchmarks import bench_io, compile_bench, serve_bench
        res = compile_bench.run_fused(quick=args.quick)
        serve = serve_bench.run(quick=args.quick)
        res.update({f"serve_{k}": v for k, v in serve.items()
                    if k.startswith(("single_rps", "batched_rps", "b16_rps",
                                     "batch_speedup"))})
        bench_io.write_bench_json(res, benchmark="pr7",
                                  basename="BENCH_pr7.json",
                                  quick=args.quick)
    if want("pr9"):
        from benchmarks import search_bench
        search_bench.write_json(search_bench.run(quick=args.quick),
                                quick=args.quick)
    if want("pr10"):
        from benchmarks import decode_bench
        decode_bench.write_json(decode_bench.run(quick=args.quick),
                                quick=args.quick)
    if want("roofline"):
        from benchmarks import roofline
        roofline.main("base", "16x16")
    print(f"total,seconds,{time.time()-t0:.1f}")


if __name__ == "__main__":
    main()
