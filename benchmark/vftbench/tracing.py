"""A profiler trace of a steady sub-window, and its reduction to busy time,
idle gaps and per-operation self time.

Only the device is traced. With the host tracer on, the runtime's own threads
wrote 2.2 million events a second while raft-files copied flow out (590 MB
for eight seconds, minutes to write and read), and the Python tracer hooks
every call of every thread. So the two clocks are tied without host events:
the trace counts time from the start of its session, which falls inside the
``start_trace`` call, and ``perf_counter`` is read on both sides of that call.
The offset is then known to within the call's length, 48 to 66 ms on the
v5e's host. (One run with a host anchor put the session's start 0.13 ms after
the reading before the call; the bound used is the call's whole length all
the same.) An idle gap is laid over what the host was doing only where it is
several times longer than that bound; a shorter one says ``host: unknown``.
The reduction below the loader is plain arithmetic on
lists of ``(name, start_ns, duration_ns)`` and is what the tests exercise.
"""
from __future__ import annotations

import re
import time
from pathlib import Path
from typing import Any, Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_ns, duration_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
UNKNOWN = "host: unknown"
#: a gap is attributed only if it is this many times the clocks' uncertainty
SOUND_GAP = 4.0


# -- capture ------------------------------------------------------------------

class TraceWindow:
    """``start()`` ... ``stop()`` around a few steady seconds. The sub-window
    is ``[open_perf, close_perf]``: after ``start_trace`` has returned and
    before ``stop_trace`` is called, so the trace covers all of it. What the
    reduction and the timeline need later is kept on the measurement ``m``:
    the file, ``perf_counter`` on both sides of ``start_trace`` and before
    ``stop_trace``."""

    def __init__(self, out_dir: Path, m) -> None:
        self.out_dir = Path(out_dir)
        self.m = m
        self.stop_s = 0.0  # how long ``stop_trace`` took

    def start(self) -> None:
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        options.enable_hlo_proto = False
        self.m.trace_zero_perf = time.perf_counter()
        jax.profiler.start_trace(str(self.out_dir), profiler_options=options)
        self.m.trace_open_perf = time.perf_counter()

    def stop(self) -> None:
        import jax
        self.m.trace_close_perf = time.perf_counter()
        jax.profiler.stop_trace()
        self.stop_s = time.perf_counter() - self.m.trace_close_perf
        found = sorted(self.out_dir.rglob("*.xplane.pb"))
        if not found:
            raise RuntimeError(f"the profiler wrote no .xplane.pb under "
                               f"{self.out_dir}")
        self.m.trace_path = found[-1]

    def took(self) -> str:
        start_s = self.m.trace_open_perf - self.m.trace_zero_perf
        return (f"start_trace took {start_s:.3f} s and stop_trace "
                f"{self.stop_s:.3f} s")


# -- loading ------------------------------------------------------------------

_HLO = re.compile(r"^(%[^ ]+) = .*?\s([a-z][\w-]*)\(")


def short_name(name: str) -> str:
    """``%fusion.54 = bf16[...] fusion(...), kind=...`` -> ``%fusion.54
    fusion``: the TPU plane names an operation by its whole HLO line."""
    hit = _HLO.match(name)
    return f"{hit.group(1)} {hit.group(2)}" if hit else name[:120]


def load_profile(profile) -> Dict[str, Dict[str, List[Event]]]:
    """``{plane: {line: [(name, start_ns, duration_ns)]}}`` of the device
    planes; times count from the start of the profiler's session."""
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (short_name(ev.name), float(ev.start_ns),
                 float(ev.duration_ns)) for ev in line.events)
    return out


def load_xplane(path: Path) -> Dict[str, Dict[str, List[Event]]]:
    from jax.profiler import ProfileData
    return load_profile(ProfileData.from_file(str(path)))


# -- reduction ----------------------------------------------------------------

def busy_union(intervals: Iterable[Tuple[float, float]]
               ) -> List[Tuple[float, float]]:
    """Merge ``(start, end)`` intervals: overlapping or nested operations
    count once."""
    merged: List[Tuple[float, float]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def clip(merged: Sequence[Tuple[float, float]], t0: float, t1: float
         ) -> List[Tuple[float, float]]:
    return [(max(s, t0), min(e, t1)) for s, e in merged
            if min(e, t1) > max(s, t0)]


def total(merged: Sequence[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in merged)


def idle_gaps(merged: Sequence[Tuple[float, float]], t0: float, t1: float
              ) -> List[Tuple[float, float]]:
    """``(start, end)`` of every stretch of ``[t0, t1]`` no operation covers,
    longest first."""
    gaps, cursor = [], t0
    for s, e in clip(merged, t0, t1):
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if t1 > cursor:
        gaps.append((cursor, t1))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def self_times(events: Iterable[Event]) -> Dict[str, float]:
    """Nanoseconds of self time per operation name on one line: an
    operation's duration less what the operations nested inside it cover
    (a ``while`` holds its body's operations)."""
    out: Dict[str, float] = {}
    stack: List[List[Any]] = []  # [name, end, self]

    def close(entry: List[Any]) -> None:
        out[entry[0]] = out.get(entry[0], 0.0) + max(entry[2], 0.0)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            close(stack.pop())
        if stack:
            # the part of this event inside its parent is not the parent's
            stack[-1][2] -= min(start + dur, stack[-1][1]) - start
        stack.append([name, start + dur, dur])
    while stack:
        close(stack.pop())
    return out


def attribute(gap: Tuple[float, float],
              host: Iterable[Tuple[str, float, float]]) -> str:
    """The host span (``name, start, end`` on the gap's clock) that covers
    most of ``gap``, or ``"host: unknown"`` when none overlaps it."""
    cover: Dict[str, float] = {}
    for name, start, end in host:
        overlap = min(end, gap[1]) - max(start, gap[0])
        if overlap > 0:
            cover[name] = cover.get(name, 0.0) + overlap
    if not cover:
        return UNKNOWN
    return "host: " + max(cover, key=cover.get)


def reduce_trace(planes: Dict[str, Dict[str, List[Event]]],
                 open_s: float, close_s: float,
                 host_spans: Sequence[Tuple[str, float, float]] = (),
                 uncertainty_s: float = 0.0, chips: int = 1
                 ) -> Dict[str, Any]:
    """Busy seconds, window seconds, the ten operations with most self time
    and the five longest idle gaps of the sub-window ``[open_s, close_s]``.

    Every time is in seconds from the start of the profiler's session:
    ``host_spans`` are ``(name, start, duration)`` already moved onto that
    clock, and ``uncertainty_s`` says how well."""
    if not planes:
        raise ValueError("the trace holds no /device:TPU:<n> plane")
    t0, t1 = open_s * 1e9, close_s * 1e9
    per_device = []
    for plane in sorted(planes)[:chips]:
        if OPS_LINE not in planes[plane]:
            raise ValueError(f"{plane} has no line {OPS_LINE!r}; lines: "
                             f"{sorted(planes[plane])}")
        per_device.append(planes[plane][OPS_LINE])
    busy_ns = [total(clip(busy_union((s, s + d) for _, s, d in events),
                          t0, t1)) for events in per_device]
    if not any(busy_ns):
        raise ValueError("no operation ran on the device inside the "
                         "sub-window")
    first = per_device[0]
    selfs = self_times(e for e in first if e[1] + e[2] > t0 and e[1] < t1)
    host_ns = [(n, s * 1e9, (s + d) * 1e9) for n, s, d in host_spans]
    gaps = idle_gaps(busy_union((s, s + d) for _, s, d in first), t0, t1)[:5]
    sound_ns = SOUND_GAP * uncertainty_s * 1e9
    top = sorted(selfs.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "window_s": (t1 - t0) / 1e9,
        "clock_uncertainty_s": uncertainty_s,
        "device_ops": [[name, ns / 1e9] for name, ns in top],
        "idle_gaps": [[attribute(g, host_ns) if g[1] - g[0] >= sound_ns
                       else UNKNOWN, (g[1] - g[0]) / 1e9] for g in gaps],
        "self_s": {name: ns / 1e9 for name, ns in selfs.items()},
    }
