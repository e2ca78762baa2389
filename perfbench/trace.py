"""Reduction of a JAX profiler trace (``.xplane.pb``) to device time per
program, the device's busy union, and its idle gaps named by what the host
was doing.

Device events are the program executions of each device: on a TPU the
``XLA Modules`` line of every ``/device:TPU:<n>`` plane.  A trace recorded
on the CPU has no device planes; there the executions are the events of the
PjRt CPU client's threads, so the reduction can be checked without a chip.
Host spans are the ``TraceAnnotation`` events whose names start with the
benchmark's prefix, from any line of the host plane.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

PREFIX = "perfbench."
_SUFFIX = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float]


@dataclass
class Summary:
    programs: Dict[str, float]            # program name -> device seconds
    calls: Dict[str, int]                 # program name -> executions
    busy_s: float                         # union of device intervals, mean over devices
    window_s: float
    gaps: List[Tuple[str, float]] = field(default_factory=list)


def find_xplane(directory: str) -> str:
    files = sorted(glob.glob(os.path.join(directory, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def program_name(name: str) -> str:
    """A module name without the run id the profiler appends."""
    return _SUFFIX.sub("", name.strip())


def device_events(pd) -> Dict[str, List[Tuple[str, float, float]]]:
    """device -> [(program, start_ns, end_ns)]."""
    out: Dict[str, List[Tuple[str, float, float]]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    out[plane.name] = [
                        (program_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events]
    if out:
        return out
    cpu = []
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name.startswith("tf_XLAPjRtCpuClient"):
                    cpu += [(program_name(e.name), e.start_ns,
                             e.start_ns + e.duration_ns)
                            for e in line.events if e.duration_ns > 0
                            and not e.name.startswith(("end: ",
                                                       "ThunkExecutor",
                                                       "Threadpool"))]
    return {"/host:CPU": cpu} if cpu else {}


def host_spans(pd) -> List[Tuple[str, float, float]]:
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if e.name.startswith(PREFIX)]
    return sorted(out, key=lambda s: s[1])


def union(intervals: List[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle stretches of [lo, hi] between merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def host_label(spans, t: float) -> str:
    """The innermost benchmark span that holds instant ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "no host span"


def summarize(pd, window: Interval = None, top: int = 10) -> Summary:
    """Device seconds per program, busy and window seconds and the ``top``
    longest idle gaps over ``window`` (ns; default: first to last device
    event)."""
    devs = device_events(pd)
    if not devs:
        raise ValueError("the trace holds no device events")
    spans = host_spans(pd)
    if window is None:
        starts = [s for evs in devs.values() for _, s, _ in evs]
        ends = [e for evs in devs.values() for _, _, e in evs]
        window = (min(starts), max(ends))
    lo, hi = window
    programs: Dict[str, float] = {}
    calls: Dict[str, int] = {}
    busy_total = 0.0
    all_gaps = []
    for evs in devs.values():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                  if e > lo and s < hi]
        for n, s, e in inside:
            programs[n] = programs.get(n, 0.0) + (e - s) * 1e-9
            calls[n] = calls.get(n, 0) + 1
        busy = union([(s, e) for _, s, e in inside])
        busy_total += sum(e - s for s, e in busy)
        all_gaps += gaps(busy, lo, hi)
    all_gaps.sort(key=lambda g: g[0] - g[1])
    named = [(host_label(spans, (s + e) / 2), (e - s) * 1e-9)
             for s, e in all_gaps[:top]]
    return Summary(programs=programs, calls=calls,
                   busy_s=busy_total * 1e-9 / len(devs),
                   window_s=(hi - lo) * 1e-9, gaps=named)


def span_window(pd, first: str, last: str) -> Interval:
    """From the start of the first host span named ``first`` to the end of
    the last span named ``last``."""
    spans = host_spans(pd)
    starts = [s for n, s, _ in spans if n == first]
    ends = [e for n, _, e in spans if n == last]
    if not starts or not ends:
        raise ValueError(f"trace lacks host spans {first!r} / {last!r}")
    return min(starts), max(ends)


def seconds_matching(summary: Summary, patterns) -> float:
    """Device seconds of the programs whose name matches any of the
    regular expressions in ``patterns``."""
    rx = [re.compile(p) for p in patterns]
    return sum(t for n, t in summary.programs.items()
               if any(r.search(n) for r in rx))
