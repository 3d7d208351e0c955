"""Operations and bytes of the benchmark's kernels, computed from shapes.

Nothing here reads the program: the counts follow from the layer shapes of
the model (rows fed, K, N, threshold levels), so they do not change when a
later kernel picks other block sizes, padding or packing.  A model's own
shapes and MACs live in its plain reference, ``bench/configs/<model>.py``
(``weight_matmuls(cfg)``, ``macs_per_frame(cfg)``).
"""

from __future__ import annotations

from typing import Dict, Tuple


def passes(cfg: Dict) -> int:
    """Forward passes per served frame: 2 with the EASY flip ensemble (the
    frame and its mirror image, whose features are summed), else 1."""
    return 2 if cfg["easy_augment"] else 1


def frame_ops(model, cfg: Dict) -> int:
    """Operations (2 per MAC) the model needs per served frame.  ``model``
    is the configuration's plain reference (``bench/configs/<model>.py``),
    whose ``macs_per_frame(cfg)`` counts one forward pass."""
    return 2 * passes(cfg) * model.macs_per_frame(cfg)


def mvau_int_work(rows: int, k: int, n: int, levels: int) -> Tuple[int, int]:
    """``(ops, bytes)`` of one integer MVAU call: ``rows x K`` int8 codes
    times ``K x N`` int8 weight codes (2 operations per MAC), with ``N x
    levels`` int32 thresholds read and ``rows x N`` int32 codes written."""
    ops = 2 * rows * k * n
    nbytes = rows * k + k * n + 4 * n * levels + 4 * rows * n
    return ops, nbytes


def mvau_int_bound_s(rows: int, k: int, n: int, levels: int,
                     int8_ops_per_s: float, bytes_per_s: float) -> float:
    """The least time the chip could take for one integer MVAU call: the
    larger of its operations at the int8 peak and its bytes at the HBM
    bandwidth."""
    ops, nbytes = mvau_int_work(rows, k, n, levels)
    return max(ops / int8_ops_per_s, nbytes / bytes_per_s)
