"""The start-up ledger: what the process did before its first feature.

Process-wide and always on. Two bounded lists under one lock, both stamped
with ``time.perf_counter()`` (the clock of ``TraceRecorder.perf0`` and of the
benchmark's window, so ledger, spans and window need no offset):

  - **phases**, recorded where the work happens, at the seams every family
    shares: ``backend`` (extractors/base.py: the first touch of the
    backend), ``cache_attach`` (compile_cache.py), ``params``
    (weights/store.py ``resolve_params``, models/token_rows.py
    ``init_params``), ``place`` (parallel/mesh.py: the host side of the
    asynchronous ``device_put`` of the parameters), ``first_dispatch`` (the
    first enqueue of each padded shape of a runner) and the instant
    ``ready`` (cli.py, serve.py: the extractor is built). Where a
    :class:`~.trace.TraceRecorder` runs, a phase is also the span
    ``startup.<name>`` of its timeline.
  - **records**, one for every arrival of ``jax.monitoring``'s three compile
    stages (``trace``, ``lower``, ``compile``: the last is a compile or a load
    from the persistent cache) with the function's name, and one stamp for
    every persistent-cache ``hit`` and ``miss``. ``end`` is read as the
    listener is called, so ``[end - dur, end]`` lies beside the phases. A
    ``jit`` traced inside another's trace (or lowering) reports its own
    trace inside the outer stage's, just before it: the outer record
    replaces those at the list's end, and where another thread's record
    stands between them both stay, so read unions of intervals
    (:func:`union_s`), never sums.

``jax.monitoring`` listeners cannot be unregistered, so :func:`install`
registers them once a process; ``BaseExtractor.__init__`` calls it before it
touches the backend, and ``recorder.compile_cache_baseline`` as it always
did. Importing this module imports no JAX and registers nothing.

Who reads it: :func:`summary` is ``_run.json``'s ``startup`` key and
``vft-serve``'s start line (:func:`ready_line`); the benchmark's
``setup.*`` metrics read :func:`snapshot` cut at the window's first instant
(``benchmark/vftbench/startup.py``). docs/observability.md "Reading a slow
start".
"""
from __future__ import annotations

import os
import re
import threading
import time
from typing import Any, Dict, Iterable, List, NamedTuple, Optional, Tuple

from . import trace as _trace

#: first N kept; what does not fit is counted in ``dropped``
MAX_PHASES = 256
MAX_RECORDS = 8192

#: the three stages of ``jax/_src/dispatch.py`` every program goes through
COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
STAGES = tuple(COMPILE_STAGES.values())
#: the runners' jitted steps (parallel/mesh.py ``step_program_name``)
STEP_PREFIX = "vft_"
#: tracing reports ``fun_name=f``, lowering and compiling ``jit(f)``
_WRAPPED = re.compile(r"^\w+\((.*)\)$")


class Phase(NamedTuple):
    name: str
    start: float          # perf_counter
    dur: float
    cpu: float            # the thread's CPU seconds (``time.thread_time()``)
    detail: Dict[str, Any]   # the call site's keyword arguments


class Record(NamedTuple):
    stage: str            # trace | lower | compile | hit | miss
    fun_name: str         # "" for a hit or a miss
    end: float            # perf_counter as the listener was called
    dur: float            # 0.0 for a hit or a miss
    tid: int


_lock = threading.Lock()
_phases: List[Phase] = []
_records: List[Record] = []
_dropped = {"phases": 0, "records": 0}
#: ``/jax/compilation_cache/*`` events by name: what
#: ``recorder.compile_cache_summary`` reads (the same counts, not a second one)
_cache_events: Dict[str, int] = {}
#: summed ``cache_retrieval_time_sec`` / ``compile_time_saved_sec``
_cache_seconds = {"retrieval_s": 0.0, "saved_s": 0.0}
_installed = False
_IMPORTED = time.perf_counter()
_process_start: Optional[float] = None


def process_start() -> float:
    """When this process started, on the ``perf_counter`` clock: from
    ``/proc`` (read once), and where that cannot be read the instant this
    module was imported."""
    global _process_start
    if _process_start is None:
        _process_start = _read_process_start()
    return _process_start


def _read_process_start() -> float:
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        if age >= 0.0:
            return time.perf_counter() - age
    except (OSError, ValueError, IndexError):
        pass
    return _IMPORTED


# -- writing -----------------------------------------------------------------

def _add_phase(p: Phase) -> None:
    with _lock:
        if len(_phases) < MAX_PHASES:
            _phases.append(p)
        else:
            _dropped["phases"] += 1


def _add_record(r: Record) -> None:
    with _lock:
        if r.dur > 0.0:
            # a jit traced inside this trace (or inside this lowering: a
            # primitive lowered through a traced helper) reported just
            # before it, and its interval lies inside this one's: the outer
            # record stands for both (RAFT's steps alone left 14,000 such
            # records, PR 36)
            began = r.end - r.dur
            while _records and _records[-1].stage == "trace" \
                    and _records[-1].tid == r.tid \
                    and _records[-1].end - _records[-1].dur >= began:
                _records.pop()
        if len(_records) < MAX_RECORDS:
            _records.append(r)
        else:
            _dropped["records"] += 1


class phase:
    """``with startup.phase("params", model_key=...):`` appends one
    :class:`Phase` on exit (exceptional exits included) and, where a
    ``TraceRecorder`` runs, is the span ``startup.<name>``."""

    __slots__ = ("_name", "_args", "_span", "_t0", "_c0")

    def __init__(self, name: str, **args: Any) -> None:
        self._name = name
        self._args = args

    def __enter__(self) -> "phase":
        self._span = _trace.span("startup." + self._name, **self._args)
        self._span.__enter__()
        self._c0 = time.thread_time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        dur = time.perf_counter() - self._t0
        cpu = time.thread_time() - self._c0
        _add_phase(Phase(self._name, self._t0, dur, cpu, self._args))
        self._span.__exit__(exc_type, exc, tb)
        return None


def mark(name: str, **args: Any) -> None:
    """An instant: a phase of no length (``ready``)."""
    _trace.instant("startup." + name, **args)
    _add_phase(Phase(name, time.perf_counter(), 0.0, 0.0, args))


def install() -> None:
    """Register the ``jax.monitoring`` listeners, once a process."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    try:
        from jax import monitoring
    except Exception:
        return  # telemetry degrades, extraction does not

    def on_event(event: str, **kw) -> None:
        if "compilation_cache" not in event:
            return
        with _lock:
            _cache_events[event] = _cache_events.get(event, 0) + 1
        stage = ("hit" if event.endswith("cache_hits") else
                 "miss" if event.endswith("cache_misses") else None)
        if stage is not None:
            _add_record(Record(stage, "", time.perf_counter(), 0.0,
                               threading.get_ident()))

    def on_duration(event: str, duration: float, **kw) -> None:
        stage = COMPILE_STAGES.get(event)
        if stage is not None:
            name = str(kw.get("fun_name", ""))
            wrapped = _WRAPPED.match(name)
            _add_record(Record(stage, wrapped.group(1) if wrapped else name,
                               time.perf_counter(), float(duration),
                               threading.get_ident()))
        elif "compilation_cache" in event:
            with _lock:
                _cache_events[event] = _cache_events.get(event, 0) + 1
                if event.endswith("cache_retrieval_time_sec"):
                    _cache_seconds["retrieval_s"] += float(duration)
                elif event.endswith("compile_time_saved_sec"):
                    _cache_seconds["saved_s"] += float(duration)

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)


def cache_event_counts() -> Dict[str, int]:
    """``{event: arrivals}`` of the ``/jax/compilation_cache/*`` events."""
    with _lock:
        return dict(_cache_events)


# -- reading -----------------------------------------------------------------

def snapshot(until: Optional[float] = None) -> Dict[str, Any]:
    """The ledger up to the instant ``until`` (``perf_counter``; ``None``:
    now): the phases that began and the records that ended by then, in the
    order they were appended."""
    with _lock:
        phases, records = list(_phases), list(_records)
        dropped, seconds = dict(_dropped), dict(_cache_seconds)
    if until is not None:
        phases = [p for p in phases if p.start <= until]
        records = [r for r in records if r.end <= until]
    return {"process_start": process_start(),
            "phases": phases, "records": records, "dropped": dropped,
            "cache_hits": sum(r.stage == "hit" for r in records),
            "cache_misses": sum(r.stage == "miss" for r in records),
            # whole-process sums: a duration carries no instant to cut at
            **seconds}


def union_s(intervals: Iterable[Tuple[float, float]]) -> float:
    """Seconds covered by ``(start, end)`` intervals, overlaps once."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


def _spans(records: Iterable[Record]) -> List[Tuple[float, float]]:
    return [(r.end - r.dur, r.end) for r in records]


def summary(snap: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """What an operator reads of a snapshot (``_run.json``'s ``startup``):
    seconds by phase, the programs compiled or loaded with their trace /
    lower / compile-or-load seconds split into the runners' steps and
    everything else, the cache's hits, misses and seconds, the ten names
    with most seconds and every first dispatch (program, padded rows,
    seconds). Seconds are unions on the wall clock."""
    snap = snapshot() if snap is None else snap
    phases: Dict[str, Dict[str, float]] = {}
    for name in dict.fromkeys(p.name for p in snap["phases"]):
        hits = [p for p in snap["phases"] if p.name == name]
        phases[name] = {
            "s": round(union_s((p.start, p.start + p.dur) for p in hits), 6),
            "cpu_s": round(sum(p.cpu for p in hits), 6), "calls": len(hits)}
    staged = [r for r in snap["records"] if r.stage in STAGES]
    split: Dict[str, List[Record]] = {"steps": [], "other": []}
    for r in staged:
        split["steps" if r.fun_name.startswith(STEP_PREFIX)
              else "other"].append(r)
    out: Dict[str, Any] = {"phases": phases}
    # which wire shape of which step a late compile belongs to
    out["first_dispatches"] = [
        [p.detail["program"], p.detail["padded_rows"], round(p.dur, 6)]
        for p in snap["phases"] if p.name == "first_dispatch"][:32]
    ready = [p.start for p in snap["phases"] if p.name == "ready"]
    out["ready_s"] = round(ready[0] - snap["process_start"], 6) \
        if ready else None
    out["programs"] = sum(r.stage == "compile" for r in staged)
    out["programs_s"] = round(union_s(_spans(staged)), 6)
    for key, records in split.items():
        out[key] = {"programs": sum(r.stage == "compile" for r in records),
                    **{f"{stage}_s": round(union_s(_spans(
                        r for r in records if r.stage == stage)), 6)
                       for stage in STAGES}}
    by_name: Dict[str, List[Record]] = {}
    for r in staged:
        by_name.setdefault(r.fun_name, []).append(r)
    top = sorted(((round(union_s(_spans(rs)), 6), name)
                  for name, rs in by_name.items()), reverse=True)[:10]
    out.update(cache_hits=snap["cache_hits"],
               cache_misses=snap["cache_misses"],
               cache_retrieval_s=round(snap["retrieval_s"], 6),
               cache_saved_s=round(snap["saved_s"], 6),
               top=[[name, s] for s, name in top],
               dropped=dict(snap["dropped"]))
    return out


def ready_line(s: Optional[Dict[str, Any]] = None) -> str:
    """``ready in 21.4 s (backend 3.1, params 6.0, place 0.2, 181 programs
    9.8 s, 0 cache misses)``: the start line's account of a start."""
    s = summary() if s is None else s

    def of(name: str) -> str:
        return f"{s['phases'].get(name, {}).get('s', 0.0):.1f}"

    ready = "?" if s["ready_s"] is None else f"{s['ready_s']:.1f}"
    return (f"ready in {ready} s (backend {of('backend')}, params "
            f"{of('params')}, place {of('place')}, {s['programs']} programs "
            f"{s['programs_s']:.1f} s, {s['cache_misses']} cache misses)")
