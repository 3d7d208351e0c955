"""Median over runs of classifies of the engine's ``serve.fulfil`` span:
resolving the run's futures, client callbacks included.  ``None`` where the
program emits no such span."""

import stats


def read(run):
    return stats.percentile([e["dur_ms"] for e in run.spans("serve.fulfil")],
                            50)
