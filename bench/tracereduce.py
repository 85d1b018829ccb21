"""Reduction of a profiler trace to device busy time, program time and
idle gaps, shared by every per-layer metric that reads the trace.

A trace is first normalised to plain data (``normalise``):

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns], ...]}]}]}

so the reduction can be tested on a small recorded trace kept with the
benchmark (``tests/data``).  Device planes are named ``/device:<KIND>:<n>``;
their ``XLA Ops`` line holds one event per operation run on the device
and their ``XLA Modules`` line one event per compiled program run.
Host planes hold the ``jax.profiler.TraceAnnotation`` regions, which is
how the benchmark's own job window and the program's ``fused_chunk``
annotation appear on the profiler's clock.
"""
from __future__ import annotations

import glob
import os
import re
from statistics import median
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]            # (start_ns, end_ns)


def normalise(profile) -> dict:
    """Plain-data copy of a ``jax.profiler.ProfileData``."""
    planes = []
    for plane in profile.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name,
                          "events": [[e.name, float(e.start_ns),
                                      float(e.duration_ns)]
                                     for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load_dir(trace_dir: str) -> dict:
    """Normalised copy of the newest ``*.xplane.pb`` under a directory
    written by ``jax.profiler.trace``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return normalise(ProfileData.from_file(max(files,
                                               key=os.path.getmtime)))


def device_planes(trace: dict) -> List[dict]:
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def _line(plane: dict, name: str) -> List[list]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def host_events(trace: dict, name: str) -> List[Interval]:
    """Intervals of every host-side event called ``name``, in order."""
    out = []
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            out += [(s, s + d) for n, s, d in line["events"] if n == name]
    return sorted(out)


def clip(intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    a, b = window
    return [(max(s, a), min(e, b)) for s, e in intervals
            if min(e, b) > max(s, a)]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def busy(plane: dict, window: Interval) -> List[Interval]:
    """Merged intervals in which some operation ran on this device."""
    ops = [(s, s + d) for _, s, d in _line(plane, OPS_LINE)]
    return union(clip(ops, window))


def gaps(busy_intervals: Sequence[Interval], window: Interval
         ) -> List[Interval]:
    """The idle intervals of the window between busy intervals."""
    out, t = [], window[0]
    for s, e in busy_intervals:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if window[1] > t:
        out.append((t, window[1]))
    return out


def _short(module: str) -> str:
    """``jit__fused_scbf_rounds(123)`` -> ``jit__fused_scbf_rounds``."""
    return re.sub(r"\(\d+\)$", "", module)


def intersect(xs: Sequence[Interval], ys: Sequence[Interval]
              ) -> List[Interval]:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        s, e = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if e > s:
            out.append((s, e))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def op_time(plane: dict, window: Interval,
            module: Optional[str] = None) -> float:
    """Device time (ns) in which operations ran inside the window, only
    inside runs of programs whose name contains ``module`` when given.
    Overlapping operations (a loop and the operations of its body)
    count once."""
    ops = busy(plane, window)
    if module is None:
        return total(ops)
    mods = union(clip([(s, s + d) for n, s, d in _line(plane, MODULES_LINE)
                       if module in n], window))
    return total(intersect(ops, mods))


def top_ops(plane: dict, window: Interval, n: int = 10
            ) -> List[Tuple[str, float]]:
    """The ``n`` operations that took most device time in the window,
    named ``program/op`` (the HLO instruction's name), with their summed
    seconds."""
    mods = [(s, s + d, _short(name))
            for name, s, d in _line(plane, MODULES_LINE)]
    acc: Dict[str, float] = {}
    for name, s, d in _line(plane, OPS_LINE):
        iv = clip([(s, s + d)], window)
        if not iv:
            continue
        owner = next((m for a, b, m in mods if a <= s < b), "")
        op = name.split(" = ")[0]
        key = f"{owner}/{op}" if owner else op
        acc[key] = acc.get(key, 0.0) + total(iv) / 1e9
    return sorted(acc.items(), key=lambda kv: -kv[1])[:n]


def align(recorder_starts: Sequence[float],
          profiler_starts: Sequence[float]) -> Optional[float]:
    """Offset (ns) that maps recorder times onto the profiler's clock,
    from one region seen by both (the ``fused_chunk`` annotation)."""
    if not recorder_starts or len(recorder_starts) != len(profiler_starts):
        return None
    return median(p - r for r, p in zip(recorder_starts, profiler_starts))


def name_gaps(idle: Sequence[Interval],
              spans: Sequence[Tuple[str, float, float]],
              n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest idle stretches, each named by the innermost
    (shortest) host span covering it, ``none`` where no span does;
    seconds.  A gap that spans several host regions is cut at their
    edges, and neighbouring pieces under the same name stay joined."""
    pieces = []
    for a, b in idle:
        cuts = sorted({a, b} | {t for _, s, e in spans for t in (s, e)
                                if a < t < b})
        run = None
        for s, e in zip(cuts, cuts[1:]):
            mid = (s + e) / 2
            cover = [(se - ss, name) for name, ss, se in spans
                     if ss <= mid < se]
            name = min(cover)[1] if cover else "none"
            if run and run[0] == name:
                run[2] = e
            else:
                if run:
                    pieces.append(run)
                run = [name, s, e]
        if run:
            pieces.append(run)
    pieces.sort(key=lambda p: p[1] - p[2])
    return [(name, (e - s) / 1e9) for name, s, e in pieces[:n]]
