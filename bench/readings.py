"""Readings of the comparison that decides ``correct``, for setting limits.

  python3 bench/readings.py --workload <cell> --seeds 1,2,3 \\
      --controls 1,2,3 [--control bf16,high] --seconds 3

For each seed, in this one process: a run of the cell as ``run.py`` makes
it (set-up, a window at the cell's own load, the comparison), then, for the
seeds in ``--controls``, the same comparison with the reference computed
one step lower in precision standing in the program's place (the
configuration's ``control``, or each of ``--control``).  One JSON line per
seed; a run on a machine without an accelerator refuses like ``run.py``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run as runmod  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--control", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    import harness

    cell = harness.resolve(harness.load_benchmark(runmod.ROOT), args.workload,
                           runmod.ROOT)
    jax = runmod.configure_jax()
    if jax.devices()[0].platform != "tpu":
        print("readings: JAX finds no TPU; nothing was run", file=sys.stderr)
        return 2
    controls = {int(s) for s in args.controls.split(",") if s}
    kinds = [c for c in args.control.split(",") if c] or [cell.cfg["control"]]
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = harness.run_cell(cell, seed, args.seconds, False, t0)
        row = {"seed": seed, "correct": out.result["correct"],
               "attempted": out.result["attempted"],
               "failed": out.result["failed"],
               "program": out.evidence["readings"],
               "setup_s": out.result["metrics"]["setup_s"]["value"]}
        if seed in controls:
            row["control"] = {k: harness.control_readings(cell, out.evidence, k)
                              for k in kinds}
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
