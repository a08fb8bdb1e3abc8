"""Reduction of a profiler trace of the traced slice to device numbers.

A trace is reduced to plain tuples first (`from_xplane`), so the same
code runs on a recorded fixture (`from_json`):

  devices  {device name: [(op name, start_ns, end_ns), ...]} from each
           accelerator plane's "XLA Ops" line: the operations that ran,
           named by their HLO instruction (``%intgemm_pallas.5``);
  host     [(span name, start_ns, end_ns), ...]: the harness's own
           TraceAnnotation spans (wait, stage, dispatch, fetch, slice).

Busy time is the union of a device's op intervals inside the slice;
idle is the rest of the slice. Each idle gap is named by the host phase
that overlaps it most ("none" when no span does).
"""

from __future__ import annotations

import bisect
import gzip
import json
from typing import Dict, List, Tuple

Interval = Tuple[str, float, float]
HOST_PHASES = ("wait", "stage", "dispatch", "fetch")
SLICE = "slice"


def from_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Interval]] = {}
    host: List[Interval] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [
                        (e.name.split(" = ")[0], e.start_ns,
                         e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_PHASES or e.name == SLICE:
                        host.append(
                            (e.name, e.start_ns, e.start_ns + e.duration_ns))
    return {"devices": devices, "host": sorted(host, key=lambda s: s[1])}


def from_json(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    return {"devices": {k: [tuple(e) for e in v]
                        for k, v in raw["devices"].items()},
            "host": [tuple(e) for e in raw["host"]]}


def to_json(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def slice_bounds(trace: dict) -> Tuple[float, float]:
    spans = [s for s in trace["host"] if s[0] == SLICE]
    if len(spans) != 1:
        raise ValueError(f"expected one {SLICE!r} span, found {len(spans)}")
    return spans[0][1], spans[0][2]


def union(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Merged (start, end) of the intervals, clipped to [lo, hi]."""
    out: List[List[float]] = []
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy, lo: float, hi: float) -> List[Tuple[float, float]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


class Phases:
    """The harness's host phase spans, searchable by time."""

    def __init__(self, host):
        self.spans = sorted((s for s in host if s[0] in HOST_PHASES),
                            key=lambda s: s[1])
        self.starts = [s[1] for s in self.spans]
        self.longest = max((e - s for _, s, e in self.spans), default=0.0)

    def of(self, gap) -> str:
        """The phase overlapping the gap most ("none" if none does)."""
        best, best_ov = "none", 0.0
        i = bisect.bisect_left(self.starts, gap[0] - self.longest)
        while i < len(self.spans) and self.spans[i][1] < gap[1]:
            name, s, e = self.spans[i]
            ov = min(e, gap[1]) - max(s, gap[0])
            if ov > best_ov:
                best, best_ov = name, ov
            i += 1
        return best


def summarize(trace: dict, kernel: str, top: int = 10) -> dict:
    """Per-device busy seconds in the slice, the slice length, the events
    of the kernel (ops whose instruction name holds ``kernel``), the top
    ops and the longest idle gaps."""
    lo, hi = slice_bounds(trace)
    busy_s, kernel_s, kernel_n = {}, {}, {}
    op_s: Dict[str, float] = {}
    all_gaps = []
    phases = Phases(trace["host"])
    for dev, ops in sorted(trace["devices"].items()):
        inside = [o for o in ops if o[1] >= lo and o[2] <= hi]
        merged = union(inside, lo, hi)
        busy_s[dev] = sum(e - s for s, e in merged) * 1e-9
        ks = [o for o in inside if kernel in o[0]]
        kernel_s[dev] = sum(e - s for _, s, e in ks) * 1e-9
        kernel_n[dev] = len(ks)
        for name, s, e in inside:
            op_s[name] = op_s.get(name, 0.0) + (e - s) * 1e-9
        all_gaps += [(phases.of(g), (g[1] - g[0]) * 1e-9)
                     for g in gaps(merged, lo, hi)]
    all_gaps.sort(key=lambda g: -g[1])
    return {
        "slice_s": (hi - lo) * 1e-9,
        "busy_s": busy_s,
        "kernel_s": kernel_s,
        "kernel_n": kernel_n,
        "device_ops": sorted(op_s.items(), key=lambda x: -x[1])[:top],
        "idle_gaps": all_gaps[:top],
        "idle_by_phase": _by_phase(all_gaps),
    }


def _by_phase(all_gaps) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for name, s in all_gaps:
        out[name] = out.get(name, 0.0) + s
    return out

