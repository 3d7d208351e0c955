"""Reduction of a profiler trace to the device numbers the benchmark reports.

The JAX profiler writes an ``.xplane.pb``.  Its timestamps count from the
start of the trace; the host's spans count in ``time.perf_counter``.  The
harness puts a ``TraceAnnotation`` named :data:`SYNC` into the trace at a
moment whose ``perf_counter`` reading it keeps, so both clocks meet.

Device operations are the events of the ``XLA Ops`` line of each
``/device:<kind>:<n>`` plane.  Busy time is the union of their intervals
inside the window; idle gaps are the rest of the window, each named by what
the host was doing at its middle.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

SYNC = "bench.sync"
OPS_LINE = "XLA Ops"
NO_SPAN = "no_serve_span_open"

Interval = Tuple[float, float]


class DeviceTrace:
    """The device operations of one trace, on the ``perf_counter`` clock.

    ``ops[d]`` lists ``(instruction name, start_s, end_s)`` for device
    ``d``, sorted by start."""

    def __init__(self, ops: Dict[str, List[Tuple[str, float, float]]]):
        self.ops = ops

    @classmethod
    def from_file(cls, path: str, sync_perf_s: float) -> "DeviceTrace":
        from jax.profiler import ProfileData

        return cls.from_profile(ProfileData.from_file(path), sync_perf_s)

    @classmethod
    def from_profile(cls, pd, sync_perf_s: float) -> "DeviceTrace":
        sync_ns = None
        ops: Dict[str, List[Tuple[str, float, float]]] = {}
        for plane in pd.planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name == SYNC:
                            sync_ns = ev.start_ns
            elif plane.name.startswith("/device:"):
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        ops[plane.name] = [(op_name(ev.name), ev.start_ns,
                                            ev.end_ns) for ev in line.events]
        if sync_ns is None:
            raise ValueError(f"trace has no {SYNC!r} annotation")
        shift = sync_perf_s - sync_ns * 1e-9
        return cls({dev: sorted(((n, s * 1e-9 + shift, e * 1e-9 + shift)
                                 for n, s, e in evs), key=lambda ev: ev[1])
                    for dev, evs in ops.items()})

    def devices(self) -> List[str]:
        return sorted(d for d, evs in self.ops.items() if evs)

    def busy_s(self, lo: float, hi: float) -> float:
        """Busy seconds in ``[lo, hi]``, averaged over the devices."""
        devs = self.devices()
        if not devs:
            return 0.0
        return sum(_length(_union(self.ops[d], lo, hi)) for d in devs) / len(devs)

    def idle_gaps(self, lo: float, hi: float) -> List[Interval]:
        """Intervals of ``[lo, hi]`` in which the first device ran nothing."""
        devs = self.devices()
        busy = _union(self.ops[devs[0]], lo, hi) if devs else []
        gaps, t = [], lo
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        return gaps

    def op_seconds(self, lo: float, hi: float) -> Dict[str, float]:
        """Device seconds per operation name (numeric suffixes such as
        ``.12`` dropped) over every device, for operations that start in
        ``[lo, hi]``."""
        out: Dict[str, float] = {}
        for evs in self.ops.values():
            for name, s, e in evs:
                if lo <= s <= hi:
                    key = op_family(name)
                    out[key] = out.get(key, 0.0) + (e - s)
        return out

    def per_span(self, spans: Sequence[Interval], match: str
                 ) -> List[Tuple[int, float]]:
        """For each ``(start, end)`` span: how many operations whose name
        contains ``match`` start inside it, and their device seconds."""
        evs = sorted((s, e) for ops in self.ops.values()
                     for name, s, e in ops if match in name)
        starts = [s for s, _ in evs]
        out = []
        for a, b in spans:
            i, j = bisect.bisect_left(starts, a), bisect.bisect_right(starts, b)
            out.append((j - i, sum(e - s for s, e in evs[i:j])))
        return out


def op_name(text: str) -> str:
    """The instruction name of an ``XLA Ops`` event, whose name may be the
    whole HLO instruction (``%mvau_int_pallas.16 = s32[...] custom-call(...``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def op_family(name: str) -> str:
    """An instruction name without its numeric suffixes (``fusion.12`` ->
    ``fusion``)."""
    return re.sub(r"(\.\d+)+$", "", name)


def _union(evs: Iterable[Tuple[str, float, float]], lo: float,
           hi: float) -> List[Interval]:
    out: List[Interval] = []
    for _, s, e in evs:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _length(ivs: Sequence[Interval]) -> float:
    return sum(e - s for s, e in ivs)


def name_gaps(gaps: Sequence[Interval],
              spans: Sequence[Tuple[str, float, float]],
              priority: Sequence[str]) -> Dict[str, float]:
    """Idle seconds by what the host was doing at the middle of each gap:
    the first name in ``priority`` with a span open there, else
    :data:`NO_SPAN`.  Spans of other names are ignored."""
    index = {}
    for name in priority:
        ivs = sorted((s, e) for n, s, e in spans if n == name)
        reach, last = [], float("-inf")     # latest end among ivs[:i+1]
        for _, e in ivs:
            last = max(last, e)
            reach.append(last)
        index[name] = ([s for s, _ in ivs], reach)
    out: Dict[str, float] = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        label = NO_SPAN
        for name in priority:
            starts, reach = index[name]
            i = bisect.bisect_right(starts, mid) - 1
            if i >= 0 and reach[i] >= mid:
                label = name
                break
        out[label] = out.get(label, 0.0) + (b - a)
    return out


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def find_xplane(root: str) -> str:
    paths = glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {root}, "
                                f"found {len(paths)}")
    return paths[0]
