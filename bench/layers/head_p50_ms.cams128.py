"""Median over NCM head calls of the engine's ``serve.head`` span: one
``store.classify`` per run of classifies in a backbone call.  ``None``
where the program emits no such span."""

import stats


def read(run):
    return stats.percentile([e["dur_ms"] for e in run.spans("serve.head")], 50)
