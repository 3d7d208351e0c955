"""Share of its roofline that the integer MVAU kernel reached in the
window: the least time the chip could take for every ``mvau_int`` call
(``work.mvau_int_bound_s`` at the rows each call was fed, of the layer
shapes that ``weight_matmuls`` of the configuration's reference file gives)
over the device time of the ``mvau_int`` kernels in the trace.

Each backbone call (one ``serve.batch`` span, padded to its bucket) runs
every weight matmul once per forward pass: twice with the flip ensemble.  The
kernels are matched to the call whose span they start in."""

import work

KERNEL = "mvau_int"


def read(run):
    cfg = run.cfg
    if cfg["datapath"] != "int":
        return None
    batches = run.batches()
    calls = run.device.per_span(run.intervals("serve.batch"), KERNEL)
    pk = run.peaks()
    levels = 2 ** cfg["quant"]["act"]["total_bits"] - 1
    passes = work.passes(cfg)
    layers = run.model().weight_matmuls(cfg)
    bound = seconds = 0.0
    for b, (count, secs) in zip(batches, calls):
        if count != passes * len(layers):
            continue                   # a call the trace did not see whole
        rows = b["attrs"]["bucket"]
        seconds += secs
        for _, m, k, n in layers:
            bound += passes * work.mvau_int_bound_s(
                rows * m, k, n, levels, pk["int8_ops"], pk["hbm_bytes_per_s"])
    return 100.0 * bound / seconds if seconds > 0 else None
