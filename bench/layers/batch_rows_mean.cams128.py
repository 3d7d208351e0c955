"""Mean real (unpadded) rows per backbone call: ``n_real`` of the engine's
``serve.batch`` spans."""


def read(run):
    rows = [e["attrs"]["n_real"] for e in run.batches()]
    return sum(rows) / len(rows) if rows else None
