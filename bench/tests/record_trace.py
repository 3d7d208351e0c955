"""Record the small chip trace that ``test_bench.py`` checks the trace
reduction on: a short traced window of one cell, its profile and its spans.

  python3 bench/tests/record_trace.py --workload <cell> --seconds 0.3 \\
      --out <dir>
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run as runmod  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=0.3)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import harness

    cell = harness.resolve(harness.load_benchmark(runmod.ROOT), args.workload,
                           runmod.ROOT)
    jax = runmod.configure_jax()
    if jax.devices()[0].platform != "tpu":
        print("record_trace: JAX finds no TPU", file=sys.stderr)
        return 2
    out = harness.run_cell(cell, args.seed, args.seconds, True, T_START,
                           keep_trace=args.out)
    harness.emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
