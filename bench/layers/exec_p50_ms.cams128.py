"""Median over backbone calls of the engine's ``serve.exec`` span: the
deployed artifact's call with its host-to-device transfer and the copy
back.  Every request of a batch carries the same span; each call counts
once."""

import stats


def read(run):
    calls = {(e["t0"], e["dur_ms"]) for e in run.spans("serve.exec")}
    return stats.percentile([d for _, d in calls], 50)
