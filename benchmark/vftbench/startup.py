"""The program's start-up ledger (``video_features_tpu/telemetry/startup.py``)
as the benchmark reads it: what the ``setup.*`` metrics share.

The ledger is on ``time.perf_counter()``, the clock of the window, so every
reader cuts it at ``m.t0`` (the reference check after the window compiles
programs of its own before the readers run) and lays it over the set-up,
``[m.t0 - m.setup_s, m.t0]``. A phase is ``(name, start, dur, ...)``, a record
``(stage, fun_name, end, dur, tid)`` with ``stage`` one of ``trace``,
``lower``, ``compile`` (a compile or a load from the persistent cache),
``hit`` and ``miss``. A ``jit`` traced inside another's trace reports its own
trace inside the outer one's, and a compile may lie inside a phase: every
number of seconds here is the length of a union of intervals, never a sum.
On a program without the ledger :func:`snapshot` returns ``None`` and every
reader finds nothing to read.

Everything below :func:`snapshot` is arithmetic on lists and is what the
tests exercise.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Tuple

from . import tracing

Interval = Tuple[float, float]  # start, end: host seconds

#: the runners' jitted steps (``parallel/mesh.py step_program_name``)
STEP_PREFIX = "vft_"
STAGES = ("trace", "lower", "compile")


def program_module():
    """``video_features_tpu.telemetry.startup`` if the program keeps the
    ledger (since PR 36), else ``None``."""
    try:
        from video_features_tpu.telemetry import startup
    except ImportError:
        return None
    return startup if hasattr(startup, "snapshot") else None


def snapshot(m) -> Optional[Dict[str, Any]]:
    """The ledger up to the window's first instant, read once a
    measurement; ``None`` on a program without one."""
    if not hasattr(m, "_startup_snapshot"):
        ledger = program_module()
        m._startup_snapshot = None if ledger is None or m.setup_s is None \
            else ledger.snapshot(until=m.t0)
        if m._startup_snapshot is not None:
            describe(m, m._startup_snapshot)
    return m._startup_snapshot


def describe(m, snap: Dict[str, Any]) -> None:
    """Two lines for the run's log: where the set-up's named seconds lie on
    its own clock, and the programs that took most of them."""
    start, t0 = setup_window(m)
    named = tracing.clip(tracing.busy_union(
        phase_intervals(snap) + record_intervals(snap)), start, t0)
    first = named[0][0] - start if named else m.setup_s
    last = t0 - named[-1][1] if named else 0.0
    phases = ", ".join(
        f"{name} {covered_s(phase_intervals(snap, name), start, t0):.3f}"
        for name in dict.fromkeys(p[0] for p in snap["phases"]))
    print(f"vftbench: startup: set-up {m.setup_s:.3f} s: {first:.3f} s before "
          f"the program's first phase or program, {last:.3f} s after its "
          f"last; phases (s): {phases or 'none'}; dropped {snap.get('dropped')}")
    by_name: Dict[str, List[Interval]] = {}
    by_stage: Dict[str, List[Interval]] = {stage: [] for stage in STAGES}
    for stage, name, end, dur, *_ in snap["records"]:
        if stage in STAGES:
            by_name.setdefault(name, []).append((end - dur, end))
            by_stage[stage].append((end - dur, end))
    top = sorted(((covered_s(v, start, t0), k) for k, v in by_name.items()),
                 reverse=True)[:6]
    stages = ", ".join(f"{stage} {covered_s(v, start, t0):.3f}"
                       for stage, v in by_stage.items())
    print(f"vftbench: startup: {count(snap, 'compile')} programs ({stages} "
          f"s) in {len(snap['records'])} records; most seconds: "
          + ", ".join(f"{name} {s:.3f}" for s, name in top))


# -- arithmetic ---------------------------------------------------------------

def covered_s(intervals: Iterable[Interval], t0: float, t1: float) -> float:
    """Seconds of ``[t0, t1]`` that at least one interval covers."""
    return tracing.total(tracing.clip(tracing.busy_union(intervals), t0, t1))


def phase_intervals(snap: Dict[str, Any], *names: str) -> List[Interval]:
    """``(start, end)`` of the phases called ``names`` (all, given none)."""
    return [(p[1], p[1] + p[2]) for p in snap["phases"]
            if not names or p[0] in names]


def record_intervals(snap: Dict[str, Any], steps: Optional[bool] = None
                     ) -> List[Interval]:
    """``(end - dur, end)`` of the trace, lower and compile records: the
    runners' steps (``steps=True``: the name starts with ``vft_``), the rest
    (``False``) or both (``None``)."""
    return [(r[2] - r[3], r[2]) for r in snap["records"]
            if r[0] in STAGES
            and (steps is None or r[1].startswith(STEP_PREFIX) == steps)]


def count(snap: Dict[str, Any], stage: str) -> int:
    return sum(1 for r in snap["records"] if r[0] == stage)


def setup_window(m) -> Interval:
    return m.t0 - m.setup_s, m.t0


def phases_s(m, *names: str) -> Optional[float]:
    """Wall seconds of the set-up inside the phases ``names``; ``None``
    without a ledger or where the program recorded no such phase."""
    snap = snapshot(m)
    hits = phase_intervals(snap, *names) if snap is not None else []
    return covered_s(hits, *setup_window(m)) if hits else None


def programs_s(m, steps: bool) -> Optional[float]:
    """Wall seconds of the set-up spent tracing, lowering and compiling or
    loading the runners' steps (``steps``), or every other program less the
    part that lies inside a step's interval."""
    snap = snapshot(m)
    if snap is None:
        return None
    inside = covered_s(record_intervals(snap, steps=True), *setup_window(m))
    if steps:
        return inside
    return covered_s(record_intervals(snap), *setup_window(m)) - inside


def unnamed_share(m) -> Optional[float]:
    """Percent of the set-up that neither a phase nor a record covers."""
    snap = snapshot(m)
    if snap is None or not m.setup_s:
        return None
    named = covered_s(phase_intervals(snap) + record_intervals(snap),
                      *setup_window(m))
    return 100.0 * (m.setup_s - named) / m.setup_s
