"""The program's own timeline (``video_features_tpu/telemetry/trace.py``) as
the benchmark reads it, and what it adds to a traced run: per-span seconds
and CPU, the share of a request no span names, the program's scope of every
device operation, a clock bracket tied by the work itself, and idle gaps
attributed to the leaf span of the thread that next enqueued.

How the events get here. In a ``--trace 1`` run both drivers start the
program's recorder in memory (``program.start_recorder``) and
``trace.last_recording()`` hands the events over after the window. The
profiler's file and the readings of ``perf_counter`` around ``start_trace``
are on the measurement (``tracing.TraceWindow``). ``run.py`` calls
:func:`analysis` once after it has reduced the trace; it renames
``m.trace``'s ``device_ops`` and ``idle_gaps`` in place (the line's
``breakdown`` is built from them), keeps its result on the measurement for
the readers, and returns ``None`` on a program without the recorder: every
reader of the timeline then finds nothing to read.

Everything below :func:`analysis` is arithmetic on lists and is what the
tests exercise. Host times are seconds on ``time.perf_counter()``, trace
times seconds from the start of the profiler's session, and
``host = trace + offset``.
"""
from __future__ import annotations

import math
import sys
import traceback
from pathlib import Path
from typing import Any, Dict, Iterable, List, NamedTuple, Optional, Tuple

from . import stats, tracing, xspace

#: spans that only hold other spans: their self time is time no span names
UMBRELLAS = ("serve.request", "video_attempt", "family")
#: an estimate may be this far from the offset and still pick the alignment
HINT_SLACK_S = 0.030


class Span(NamedTuple):
    name: str
    start: float           # host seconds
    dur: float
    tid: int
    sid: Optional[str]
    parent: Optional[str]
    rid: Optional[str]
    cpu: Optional[float]   # seconds of the thread's CPU, None if not known
    args: Dict[str, Any]

    @property
    def end(self) -> float:
        return self.start + self.dur


# -- the host timeline --------------------------------------------------------

class Timeline:
    """The program's complete ('X') and counter ('C') events on the host's
    clock."""

    def __init__(self, events: Iterable[dict], perf0: float) -> None:
        self.spans: List[Span] = []
        self.counters: List[Tuple[str, float, float]] = []
        for e in events:
            at = perf0 + float(e.get("ts", 0.0)) / 1e6
            if e.get("ph") == "X":
                cpu = e.get("cpu")
                self.spans.append(Span(
                    e["name"], at, float(e.get("dur", 0.0)) / 1e6,
                    int(e.get("tid", 0)), e.get("sid"), e.get("parent"),
                    e.get("rid"), None if cpu is None else cpu / 1e6,
                    e.get("args") or {}))
            elif e.get("ph") == "C":
                for value in (e.get("args") or {}).values():
                    self.counters.append((e["name"], at, float(value)))
        self.spans.sort(key=lambda s: (s.start, -s.dur))
        self._children: Optional[Dict[str, List[Span]]] = None

    def named(self, *names: str) -> List[Span]:
        return [s for s in self.spans if s.name in names]

    def seconds(self, names: Tuple[str, ...], t0: float, t1: float
                ) -> Optional[float]:
        """Seconds of the spans called ``names`` inside ``[t0, t1]``, summed
        over threads; ``None`` where the program recorded none."""
        hits = [(s.start, s.dur) for s in self.spans if s.name in names]
        return stats.clipped_seconds(hits, t0, t1) if hits else None

    def children(self, span: Span) -> List[Span]:
        """The spans ``span`` is parent of ON ITS OWN THREAD: what its self
        time leaves out (a decode-ahead thread's spans hang under a worker's
        span but run beside it, not inside it)."""
        if self._children is None:
            self._children = {}
            for s in self.spans:
                if s.parent is not None:
                    self._children.setdefault(s.parent, []).append(s)
        return [c for c in self._children.get(span.sid, [])
                if c.tid == span.tid] if span.sid is not None else []

    def self_times(self, t0: float, t1: float
                   ) -> List[Tuple[Span, float, float]]:
        """``(span, self seconds, self CPU seconds)`` inside ``[t0, t1]`` of
        every span that touches it. A span cut by an edge counts its CPU in
        proportion to the part inside."""
        def inside(s: Span) -> float:
            return max(0.0, min(s.end, t1) - max(s.start, t0))

        def cpu_inside(s: Span) -> float:
            if s.cpu is None or s.dur <= 0:
                return 0.0
            return s.cpu * inside(s) / s.dur

        out = []
        for s in self.spans:
            if s.end < t0 or s.start > t1:
                continue
            wall = inside(s)
            kids = self.children(s)
            out.append((s, max(0.0, wall - sum(inside(c) for c in kids)),
                        max(0.0, cpu_inside(s)
                            - sum(cpu_inside(c) for c in kids))))
        return out

    def unnamed_share(self, t0: float, t1: float) -> Optional[float]:
        """Percent of the worker-thread seconds inside ``serve.request``
        (clipped to the window) that no span below the umbrellas covers."""
        selfs = self.self_times(t0, t1)
        whole = sum(max(0.0, min(s.end, t1) - max(s.start, t0))
                    for s in self.named("serve.request"))
        if whole <= 0:
            return None
        unnamed = sum(wall for s, wall, _ in selfs if s.name in UMBRELLAS)
        return 100.0 * unnamed / whole

    def cpu_named(self, t0: float, t1: float) -> Optional[float]:
        """Thread-CPU seconds inside the window that a working span (not an
        umbrella) accounts for."""
        selfs = self.self_times(t0, t1)
        if not selfs:
            return None
        return sum(cpu for s, _, cpu in selfs if s.name not in UMBRELLAS)

    def dispatches(self) -> List[Dict[str, Any]]:
        """One entry per ``mesh.enqueue``, in the order they began: ``seq``,
        ``rows``, ``padded_rows``, ``program``, ``tid``, ``start``/``end`` of
        the enqueue and ``at``, when the dispatch entered the runner (its
        ``mesh.pad`` began)."""
        pads = {(s.tid, s.args.get("seq")): s.start
                for s in self.named("mesh.pad")}
        out = []
        for s in self.named("mesh.enqueue"):
            seq = s.args.get("seq")
            out.append({"seq": seq, "tid": s.tid, "start": s.start,
                        "end": s.end, "at": pads.get((s.tid, seq), s.start),
                        "rows": int(s.args.get("rows", 0)),
                        "padded_rows": int(s.args.get("padded_rows", 0)),
                        "program": s.args.get("program")})
        return sorted(out, key=lambda d: d["start"])

    def leaf_at(self, tid: int, g0: float, g1: float) -> Optional[Span]:
        """The span of thread ``tid`` with most self time inside
        ``[g0, g1]``: the leaf that was open there longest."""
        best, most = None, 0.0
        for s, wall, _ in self.self_times(g0, g1):
            if s.tid == tid and wall > most:
                best, most = s, wall
        return best


def program_module():
    """``video_features_tpu.telemetry.trace`` if it keeps recordings for a
    listener (``last_recording``, since PR 24), else ``None``."""
    try:
        from video_features_tpu.telemetry import trace
    except ImportError:
        return None
    return trace if hasattr(trace, "last_recording") else None


def last_recorder():
    """The program's recorder of the traced stretch, or ``None`` where none
    ran (the resident driver installs no stage listener) or the program
    keeps none."""
    trace = program_module()
    return trace.last_recording() if trace is not None else None


def program_recording() -> Optional[Timeline]:
    """The program's last recording on the host's clock, or ``None`` where
    there is no recorder or it recorded nothing."""
    recorder = last_recorder()
    events = recorder.events() if recorder is not None else []
    return Timeline(events, recorder.perf0) if events else None


# -- the clocks, tied by the work ---------------------------------------------

def clock_bracket(modules: List[Tuple[float, float, Any]],
                  enqueues: List[Tuple[float, float, Any, Any]],
                  fetches: List[Tuple[float, Any]],
                  fences: List[float],
                  coarse: Tuple[float, float],
                  hint: Optional[float] = None
                  ) -> Tuple[float, float, Optional[int]]:
    """Bounds ``(lo, hi)`` on the offset and the alignment that gave them.

    ``modules`` are ``(start, end, program)`` of the device's program runs on
    the trace's clock in time order; ``enqueues`` are ``(start, end, seq,
    shape)`` of the host's calls that enqueued a program; ``fetches`` are
    ``(end, seq)`` of the host's waits for dispatch ``seq``'s output;
    ``fences`` are host instants by which every program enqueued before them
    had finished. ``coarse`` are sound bounds from elsewhere (``hi`` may be
    ``inf``) and ``hint`` an estimate that is no bound.

    The device runs programs in the order the host enqueued them. With the
    ``k``-th traced module being the ``(shift + k)``-th enqueue: it cannot
    have started before that enqueue began (a lower bound), and the wait that
    took its output, or a fence after it, cannot have ended before it ended
    (an upper bound). A device that ever waited for the host pins the first
    to the enqueue's latency, a host that ever waited for the device pins
    the second to the copy's tail. An alignment is taken only if one
    ``program`` always met one ``shape`` and the other way round (a wire
    batch is a program), and if its bounds hold the hint (else lie inside
    the coarse bracket); of those the narrowest counts. Without one the
    coarse bracket comes back with ``shift`` ``None``. Two enqueues that
    overlap in time have no known order: they bound nothing from above.
    """
    lo0, hi0 = coarse
    enq = sorted(enqueues)
    if not modules or not enq:
        return lo0, hi0, None
    rank = {e[2]: i for i, e in enumerate(enq)}
    sure = [all(enq[j][0] >= e[1] or enq[j][1] <= e[0]
                for j in (i - 1, i + 1) if 0 <= j < len(enq))
            for i, e in enumerate(enq)]
    starts = [e[0] for e in enq]
    want = (hint - HINT_SLACK_S, hint + HINT_SLACK_S) if hint is not None \
        else (lo0, hi0)
    best: Optional[Tuple[float, float, float, int]] = None
    for shift in range(-(len(modules) - 1), len(enq)):
        lo, hi, pairs = -math.inf, math.inf, 0
        met: Dict[Any, Any] = {}
        for k, (m_start, _, prog) in enumerate(modules):
            i = shift + k
            if not 0 <= i < len(enq):
                continue
            lo = max(lo, enq[i][0] - m_start)
            pairs += 1
            if prog is not None and enq[i][3] is not None:
                if met.setdefault(("p", prog), enq[i][3]) != enq[i][3] or \
                        met.setdefault(("s", enq[i][3]), prog) != prog:
                    pairs = 0
                    break
        if not pairs:
            continue
        for end, seq in fetches:
            i = rank.get(seq)
            if i is not None and sure[i] and 0 <= i - shift < len(modules):
                hi = min(hi, end - modules[i - shift][1])
        for at in fences:
            # the last enqueue that began before the fence had finished
            i = _last_before(starts, at)
            if i is not None and 0 <= i - shift < len(modules):
                hi = min(hi, at - modules[i - shift][1])
        if max(lo, want[0]) > min(hi, want[1]):
            continue
        width = min(hi, hi0) - max(lo, lo0)
        if width < 0:
            continue
        if best is None or width < best[0]:
            best = (width, lo, hi, shift)
    if best is None:
        return lo0, hi0, None
    return max(best[1], lo0), min(best[2], hi0), best[3]


def _last_before(sorted_values: List[float], at: float) -> Optional[int]:
    import bisect
    i = bisect.bisect_left(sorted_values, at) - 1
    return i if i >= 0 else None


# -- idle gaps ----------------------------------------------------------------

def attribute_gap(gap: Tuple[float, float], timeline: Timeline,
                  dispatches: List[Dict[str, Any]]
                  ) -> Tuple[str, Optional[Span]]:
    """``("host: <span>", the span)`` for an idle gap ``(start, end)`` on the
    host's clock: the leaf span open longest, inside the gap, on the thread
    that next enqueued a program (the first ``mesh.enqueue`` to end after
    the gap began). What another thread did meanwhile was not in the
    device's way. ``("host: unknown", None)`` where that thread was inside
    no span, or nothing was enqueued afterwards."""
    nxt = next((d for d in dispatches if d["end"] >= gap[0]), None)
    if nxt is None:
        return tracing.UNKNOWN, None
    leaf = timeline.leaf_at(nxt["tid"], gap[0], min(gap[1], nxt["end"]))
    if leaf is None:
        return tracing.UNKNOWN, None
    return f"host: {leaf.name}", leaf


# -- one analysis per measurement ---------------------------------------------

def read_trace(m) -> Optional[Tuple[bytes, Dict[str, Dict[str, List[Any]]]]]:
    """The profiler's file of this run (``m.trace_path``), as bytes and as
    device planes; ``None`` where there is none or it holds no operation."""
    if m.trace_path is None:
        return None
    raw = xspace.read_bytes(Path(m.trace_path))
    planes = xspace.load_ops(raw)
    return (raw, planes) if xspace.ops_line(planes) else None


def analysis(m) -> Optional[Dict[str, Any]]:
    """Everything the timeline's readers read, computed once per
    measurement (``run.py`` calls it after the trace is reduced) and kept on
    it. ``None`` where the program keeps no recording; a part that
    cannot be had (no profiler file, no device plane) is ``None`` inside.
    Never raises: a traced run must not fail for what it adds."""
    if hasattr(m, "_timeline_analysis"):
        return m._timeline_analysis
    result: Optional[Dict[str, Any]] = None
    try:
        result = _analyse(m)
    except Exception:
        traceback.print_exc(file=sys.stdout)
        print("vftbench: timeline: the analysis failed; the new metrics are "
              "left out")
    m._timeline_analysis = result
    return result


def _analyse(m) -> Optional[Dict[str, Any]]:
    if program_module() is None:
        return None
    timeline = program_recording()
    out: Dict[str, Any] = {"timeline": timeline, "device": None}
    dispatches = timeline.dispatches() if timeline is not None else []
    out["dispatches"] = dispatches
    if timeline is None:
        print("vftbench: timeline: the program's recorder recorded nothing "
              "in this window; the device's side is read all the same")
    else:
        print(f"vftbench: timeline: {len(timeline.spans)} spans and "
              f"{len(timeline.counters)} counter samples from the program's "
              f"recorder, {len(dispatches)} dispatches")
    if m.trace is None:
        return out
    traced = read_trace(m)
    if traced is None:
        print("vftbench: timeline: no profiler file with device operations; "
              "scopes and clocks are left out")
        return out
    raw, planes = traced
    ops = xspace.ops_line(planes)
    open_s = m.trace["clock_uncertainty_s"]
    close_s = open_s + m.trace["window_s"]
    selfs = xspace.self_times(ops, open_s * 1e9, close_s * 1e9)
    device: Dict[str, Any] = {
        "stage_s": xspace.stage_seconds(selfs),
        "busy_s": sum(ns for _, ns in selfs) / 1e9,
        "ops": xspace.named_ops(selfs)}
    out["device"] = device

    # -- the clocks: the program's enqueues and fetches; where its recorder
    # did not run, the harness's own reading before each dispatch (earlier
    # than the enqueue, so the lower bound still holds) and its fences
    modules = [(o.start_ns / 1e9, (o.start_ns + o.dur_ns) / 1e9, o.name)
               for o in xspace.modules(planes)]
    enqueues = [(d["start"], d["end"], d["seq"],
                 (d["program"], d["padded_rows"])) for d in dispatches] or \
        [(at, at, i, padded) for i, (at, _, padded) in enumerate(m.dispatches)]
    # sound bounds from the harness's own readings: the trace's zero lies
    # inside the ``start_trace`` call, between the reading of
    # ``perf_counter`` before it and the one after it returned
    coarse = (m.trace_zero_perf, m.trace_open_perf)
    hint = None
    session = xspace.session_unix_ns(raw)
    recorder = last_recorder()
    if session is not None and recorder is not None:
        hint = recorder.perf0 + session[0] / 1e9 - recorder.start_unix
    fetches = [(s.end, s.args.get("seq")) for s in
               (timeline.named("mesh.fetch") if timeline is not None else [])]
    fences = [at for at, _ in m.completions] if m.block_rates else []
    lo, hi, shift = clock_bracket(modules, enqueues, fetches, fences, coarse,
                                  hint)
    device.update(offset_lo=lo, offset_hi=hi, shift=shift,
                  clock_bound_s=hi - lo)
    print(f"vftbench: timeline: the clocks are tied to within "
          f"{(hi - lo) * 1e3:.3f} ms by "
          f"{'the work itself' if shift is not None else 'start_trace'} "
          f"({len(modules)} modules, alignment {shift}; the harness's own "
          f"bracket was {m.trace['clock_uncertainty_s'] * 1e3:.1f} ms)")

    # -- the breakdown, under the program's names
    offset, bound = (lo + hi) / 2.0, hi - lo
    merged = tracing.busy_union((o.start_ns, o.start_ns + o.dur_ns)
                                for o in ops)
    gaps = tracing.idle_gaps(merged, open_s * 1e9, close_s * 1e9)
    named_gaps, unnamed_s, idle_s = [], 0.0, 0.0
    for g0, g1 in gaps:
        length = (g1 - g0) / 1e9
        idle_s += length
        name, leaf = tracing.UNKNOWN, None
        if timeline is not None and length >= tracing.SOUND_GAP * bound:
            name, leaf = attribute_gap((g0 / 1e9 + offset, g1 / 1e9 + offset),
                                       timeline, dispatches)
        if name == tracing.UNKNOWN:
            unnamed_s += length
        named_gaps.append({"gap": name, "seconds": length,
                           "tid": leaf.tid if leaf else None,
                           "rid": leaf.rid if leaf else None})
    device.update(idle_s=idle_s, idle_unnamed_s=unnamed_s,
                  gaps=named_gaps[:5])
    for g in named_gaps[:5]:
        print(f"vftbench: timeline: idle {g['seconds'] * 1e3:.3f} ms "
              f"{g['gap']} (thread {g['tid']}, request {g['rid']})")
    m.trace["device_ops"] = device["ops"]
    m.trace["idle_gaps"] = [[g["gap"], g["seconds"]] for g in named_gaps[:5]]
    m.trace["timeline"] = {k: device[k] for k in (
        "stage_s", "offset_lo", "offset_hi", "shift", "clock_bound_s",
        "idle_unnamed_s", "gaps")}
    return out


# -- what the readers call ----------------------------------------------------

def host(m) -> Optional[Timeline]:
    """The program's timeline of this run, or ``None`` where it has none."""
    found = analysis(m)
    return found["timeline"] if found is not None else None


def span_s_per_unit(m, *names: str) -> Optional[float]:
    """Seconds of the program's spans ``names`` inside the window, all
    threads, per unit completed in it."""
    timeline = host(m)
    if timeline is None:
        return None
    return m.per_unit(timeline.seconds(names, m.t0, m.t1))


def stage_share(m, stage: str) -> Optional[float]:
    """Percent of the device's busy time in the traced sub-window spent in
    operations whose scope's second component is ``stage`` (``RAFT/update``
    for ``update``); ``unscoped`` is what names no stage."""
    found = analysis(m)
    device = found and found["device"]
    if not device or not device["busy_s"]:
        return None
    seconds = sum(s for name, s in device["stage_s"].items()
                  if name.split("/")[-1] == stage)
    return 100.0 * seconds / device["busy_s"]
