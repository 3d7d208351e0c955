"""Median over backbone calls of the engine's ``serve.fill`` span: from the
worker starting to build a batch (waiting for its first request included)
to closing it.  ``None`` where the program emits no such span."""

import stats


def read(run):
    return stats.percentile([e["dur_ms"] for e in run.spans("serve.fill")], 50)
