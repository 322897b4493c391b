"""Self seconds of the device's operations by the program's full scope
(``GraniteHybrid/mamba/ssd``), for the readers that want finer than the
``<family>/<stage>`` seconds ``timeline.analysis`` keeps. Read from the same
profiler file with the same functions, once per measurement. ``None`` where a
run has no trace, no device plane, or a program that names no scope."""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from . import timeline, xspace


def self_times(m) -> Optional[List[Tuple[xspace.Op, float]]]:
    """``[(operation, self nanoseconds)]`` of the traced sub-window."""
    if hasattr(m, "_scope_self_times"):
        return m._scope_self_times
    found = None
    try:
        traced = timeline.read_trace(m) if m.trace is not None else None
        if traced is not None:
            ops = xspace.ops_line(traced[1])
            open_s = m.trace["clock_uncertainty_s"]
            close_s = open_s + m.trace["window_s"]
            found = xspace.self_times(ops, open_s * 1e9, close_s * 1e9)
    except Exception as e:  # a traced run must not fail for what it adds
        print(f"vftbench: scopes: could not read the trace's scopes: {e!r}")
    m._scope_self_times = found
    return found


def scope_seconds(m) -> Optional[Dict[str, float]]:
    selfs = self_times(m)
    return xspace.scope_seconds(selfs) if selfs else None


def named(m, pattern: "re.Pattern", scope: str) -> Optional[float]:
    """Seconds of the operations of exactly ``scope`` whose own name
    (``%fusion.3 fusion``) matches ``pattern``; ``None`` where none does."""
    selfs = self_times(m)
    if not selfs:
        return None
    seconds = sum(ns for op, ns in selfs
                  if xspace.scope_of(op.op_name) == scope
                  and pattern.search(op.name)) / 1e9
    return seconds or None


def under(m, prefix: str) -> Optional[float]:
    """Seconds of the traced sub-window in operations whose scope is
    ``prefix`` or lies below it; ``None`` where nothing is."""
    found = scope_seconds(m)
    if not found:
        return None
    seconds = sum(s for scope, s in found.items()
                  if scope == prefix or scope.startswith(prefix + "/"))
    return seconds or None


def share(m, prefix: str) -> Optional[float]:
    """Percent of the device's busy time under ``prefix``."""
    seconds = under(m, prefix)
    if seconds is None or not m.trace or not m.trace.get("busy_s"):
        return None
    return 100.0 * seconds / m.trace["busy_s"]


def seconds_per_unit(m, seconds: Optional[float]) -> Optional[float]:
    """``seconds`` of the traced sub-window as seconds a unit: their share
    of the busy time times ``model.device_s_per_unit``."""
    per_unit = m.device_s_per_unit()
    if not seconds or not per_unit or not m.trace.get("busy_s"):
        return None
    return seconds / m.trace["busy_s"] * per_unit
