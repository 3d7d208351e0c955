"""Median over backbone calls of the copy-back lag: the end of the call's
``serve.exec.wait`` span (its output on the host) minus the latest end
among the device operations that started inside the call's ``serve.exec``
(from ``serve.exec.dispatch``'s start to ``serve.exec.wait``'s end, on the
call's own ``batch-`` trace).  ``None`` where the program emits no such
spans or no device operation starts inside a call."""

import bisect

import stats


def read(run):
    starts = {e["trace"]: e["t0"] for e in run.spans("serve.exec.dispatch")}
    ops = sorted((s, e) for evs in run.device.ops.values() for _, s, e in evs)
    op_starts = [s for s, _ in ops]
    lags = []
    for w in run.spans("serve.exec.wait"):
        a = starts.get(w["trace"])
        if a is None:
            continue
        b = w["t0"] + w["dur_ms"] * 1e-3
        i, j = bisect.bisect_left(op_starts, a), bisect.bisect_right(op_starts, b)
        if j > i:
            lags.append((b - max(e for _, e in ops[i:j])) * 1e3)
    return stats.percentile(lags, 50)
