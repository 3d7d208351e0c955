"""Whole-step share of the chip's peak: real frames answered per second in
the window times the model's operations per frame (``work.frame_ops``, from
the ``macs_per_frame`` of the configuration's reference file; padded rows
not counted), over the peak the configuration's datapath runs at (``peak``
in its file: int8 for the integer datapath, bf16 for float32, which at
``HIGHEST`` precision cannot reach it)."""

import work


def read(run):
    cfg = run.cfg
    fps = run.e2e["frames_per_s"]
    if fps <= 0:
        return None
    ops = work.frame_ops(run.model(), cfg)
    return 100.0 * fps * ops / run.peaks()[cfg["peak"]]
