"""Operations and bytes of the benchmark's kernels, computed from shapes.

Nothing here reads the program: the counts follow from the layer shapes of
the model (rows fed, K, N, threshold levels), so they do not change when a
later kernel picks other block sizes, padding or packing.
"""

from __future__ import annotations

from typing import List, Tuple


def resnet9_mvaus(width: int, img: int) -> List[Tuple[str, int, int, int]]:
    """The eight conv MVAUs of PEFSL ResNet-9 as ``(name, rows per frame, K,
    N)``: a 3x3 conv at stride 1 and pad 1 is an im2col matmul with one row
    per output pixel and ``K = 9 * cin``; a 2x2 max-pool halves the side
    after c1, c2 and c3."""
    w = width
    plan = [("c0", 3, w, False), ("c1", w, 2 * w, True),
            ("r1a", 2 * w, 2 * w, False), ("r1b", 2 * w, 2 * w, False),
            ("c2", 2 * w, 4 * w, True), ("c3", 4 * w, 8 * w, True),
            ("r2a", 8 * w, 8 * w, False), ("r2b", 8 * w, 8 * w, False)]
    out, side = [], img
    for name, cin, cout, pool in plan:
        out.append((name, side * side, 9 * cin, cout))
        if pool:
            side //= 2
    return out


def resnet9_macs_per_frame(width: int, img: int) -> int:
    """Multiply-accumulates of one forward pass of one frame."""
    return sum(m * k * n for _, m, k, n in resnet9_mvaus(width, img))


def frame_ops(width: int, img: int, passes: int) -> int:
    """Operations (2 per MAC) the model needs per served frame, where a
    frame's feature is the sum of ``passes`` forward passes (2 with the EASY
    flip ensemble: the frame and its mirror image)."""
    return 2 * passes * resnet9_macs_per_frame(width, img)


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
