"""The benchmark's one command.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the accelerator this process finds:
set-up (weights and frames from ``--seed``, deploy, compile, support set,
warm-up), then ``--seconds`` of the cell's traffic, then the check of every
answer against the plain reference.  Earlier lines report what the window
let in (compiles, collector pauses, generator lateness); the last lines of
standard error give each compared number beside its limit; the last line of
standard output is the result as one JSON object.  Without an accelerator,
or with fewer chips than the cell asks for, it exits 2 and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def configure_jax():
    """The persistent compile cache at a fixed path inside the checkout,
    keeping every program however fast it compiled."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness

    cell = harness.resolve(harness.load_benchmark(ROOT), args.workload, ROOT)
    jax = configure_jax()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX finds no TPU (platform {devices[0].platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        return 2
    outcome = harness.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), T_START)
    harness.emit(outcome)
    return 0


if __name__ == "__main__":
    sys.exit(main())
