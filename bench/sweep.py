"""Rate sweep that fixes an open-loop mix's rate: one set-up, then one
window per rate, each reporting refusals, latency and whether the backlog
grew.

  python3 bench/sweep.py --config <config> --traffic <open mix> \\
      --seed <n> --seconds 10 --rates 800,1200,1600

The knee is the highest rate at which a whole window saw no refusal and no
growing backlog; the mix's rate is set once, at four fifths of the lower
knee of the configurations that share it.  A backlog grows when the median
latency of the window's last fifth is more than twice that of its second
fifth plus 5 ms.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

import run as runmod  # noqa: E402


def summarize(win, rate: float, seconds: float):
    import stats
    import traffic as trafficmod

    log = win["log"]
    due = np.asarray(log.due) - win["t0"]
    done = np.asarray([d if d is not None else win["t_settled"]
                       for d in log.done]) - win["t0"]
    ok = np.asarray([s == trafficmod.OK for s in log.status])
    lat = (done - due) * 1e3
    fifth = [lat[(due >= seconds * i / 5) & (due < seconds * (i + 1) / 5)]
             for i in range(5)]
    p2, p5 = (float(np.median(f)) if len(f) else 0.0 for f in
              (fifth[1], fifth[4]))
    return {"rate": rate, "attempted": len(log), "refused":
            int(sum(s == trafficmod.REFUSED for s in log.status)),
            "not_ok": int((~ok).sum()),
            "p50_ms": stats.percentile(list(lat), 50),
            "p95_ms": stats.percentile(list(lat), 95),
            "p99_ms": stats.percentile(list(lat), 99),
            "median_2nd_fifth_ms": p2, "median_last_fifth_ms": p5,
            "backlog_grows": p5 > 2 * p2 + 5.0,
            "lateness_max_ms": log.lateness_s * 1e3}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    import harness

    cell = harness.make_cell(harness.load_benchmark(runmod.ROOT), args.config,
                             args.traffic, root=runmod.ROOT)
    jax = runmod.configure_jax()
    if jax.devices()[0].platform != "tpu":
        print("sweep: JAX finds no TPU; nothing was run", file=sys.stderr)
        return 2
    sess = harness.Session(cell, args.seed)
    print(f"set-up {time.perf_counter() - T_START:.3f} s", flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, rate=rate)
        win = sess.window(mix, args.seconds, False)
        row = summarize(win, rate, args.seconds)
        row["compiles"] = sess.watch.compiles
        row["gc_pauses"] = sess.watch.gc_pauses
        print(json.dumps(row), flush=True)
    sess.system.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
