"""Share of its roofline that the bidirectional attention kernel reached in
the window: the least time the chip could take for every attention call,
the larger of its operations at the bf16 peak and its bytes at the HBM
bandwidth (``attn_work`` of the configuration's reference file: 4·S²·d
operations a frame per layer at the real S, q|k|v read and the output
written in float32, for the real rows only), over the device time of the
``attn_full`` kernels in the trace.

Each backbone call (one ``serve.batch`` span) runs one attention kernel per
layer; the kernels are matched to the call whose span they start in, and
only calls the trace saw whole (``depth`` kernels) count.  The kernel is
integer: its MXU passes take bfloat16 codes (the weights in two passes,
their high and low bytes), its 64-wide heads fill half of the MXU's depth,
and its softmax runs on the vector unit, so it cannot reach the bf16 peak:
the reading stays well under 100%."""

KERNEL = "attn_full"


def read(run):
    cfg = run.cfg
    if "depth" not in cfg:
        return None
    depth = int(cfg["depth"])
    flops, nbytes = run.model().attn_work(cfg)
    pk = run.peaks()
    per_row = max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
    calls = run.device.per_span(run.intervals("serve.batch"), KERNEL)
    bound = seconds = 0.0
    for b, (count, secs) in zip(run.batches(), calls):
        if count != depth:
            continue                   # a call the trace did not see whole
        seconds += secs
        bound += depth * b["attrs"]["n_real"] * per_row
    return 100.0 * bound / seconds if seconds > 0 else None
