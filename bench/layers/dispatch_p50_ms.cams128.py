"""Median over backbone calls of the engine's ``serve.exec.dispatch`` span:
the deployed artifact's call until it returns (the input's transfer and the
launch), before the worker waits for the output.  ``None`` where the
program emits no such span."""

import stats


def read(run):
    return stats.percentile(
        [e["dur_ms"] for e in run.spans("serve.exec.dispatch")], 50)
