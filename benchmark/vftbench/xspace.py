"""The device plane of a profiler trace with what the operations' METADATA
says: the program's scope of every operation.

What the v5e writes (one real trace looked at by hand, PR 24; PERF.md section
6): with ``enable_hlo_proto = False`` an event's name is its whole HLO line
WITHOUT ``metadata={...}``, and an event's own stats are three numbers. The
``op_name`` that ``jax.named_scope`` and flax's module names build sits in a
stat of the event's *metadata* entry, ``tf_op`` (a string that ends in ``:``),
beside ``program_id`` (the number in the "XLA Modules" line's
``jit_<program>(<id>)``; not read here: a module event's name carries it),
``hlo_category``, ``flops`` and ``bytes_accessed``.
``jax.profiler.ProfileData`` hands out an event's own stats only, so this
file reads the ``XSpace`` message's wire format itself: varints and
length-delimited fields, nothing but the standard library. Field numbers are
those of ``xplane.proto`` (tsl/profiler/protobuf).

An operation's **scope** is its ``op_name`` less what JAX's transformations
put there (``jit(...)``, ``while``, ``body``, ``closed_call``, ...) and less
its last component, the primitive: ``jit(vft_raft_forward)/RAFT/update/while/
body/closed_call/update_block/gru/convz1/conv_general_dilated:`` is
``RAFT/update/update_block/gru/convz1``, and its **stage** is the first two
components of that, ``RAFT/update``. An operation without an ``op_name`` (or
with one that holds no scope) is ``unscoped``.
"""
from __future__ import annotations

import gzip
import re
from pathlib import Path
from typing import Any, Dict, Iterator, List, NamedTuple, Optional, Tuple

from .tracing import DEVICE_PLANE, OPS_LINE, short_name

MODULES_LINE = "XLA Modules"
UNSCOPED = "unscoped"
#: name-stack components that are JAX's, not the program's
_TRANSFORM = re.compile(
    r"^(\w+\(.*\)|while|body|cond|branch_\d+_fun|closed_call|checkpoint|"
    r"remat\d*|custom_jvp_call|custom_vjp_call\w*|core_call|pjit)$")


class Op(NamedTuple):
    name: str        # ``%fusion.48 fusion`` (tracing.short_name)
    start_ns: float  # from the start of the profiler's session
    dur_ns: float
    op_name: str     # the metadata's ``tf_op`` less its colon, or ""


# -- the wire format ----------------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, Any]]:
    """``(field number, value)`` of one message: an int for a varint, bytes
    for a length-delimited field, fixed-width fields as bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield key >> 3, value


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _stats(messages: List[bytes], stat_names: Dict[int, str]
           ) -> Dict[str, Any]:
    """``{stat name: value}`` of a list of ``XStat`` messages."""
    out: Dict[str, Any] = {}
    for raw in messages:
        key, value = None, None
        for field, v in _fields(raw):
            if field == 1:
                key = stat_names.get(v)
            elif field in (3, 4, 7):    # uint64, int64, ref
                value = v
            elif field in (5, 6):       # str, bytes
                value = v.decode("utf-8", "replace") if field == 5 else v
        if key is not None:
            out[key] = value
    return out


def _map_entry(raw: bytes) -> Tuple[int, bytes]:
    key, value = 0, b""
    for field, v in _fields(raw):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


def load_ops(data: bytes) -> Dict[str, Dict[str, List[Op]]]:
    """``{plane: {line: [Op]}}`` of the ``/device:TPU:<n>`` planes of a
    serialized ``XSpace``."""
    out: Dict[str, Dict[str, List[Op]]] = {}
    for field, plane in _fields(data):
        if field != 1:
            continue
        name, lines, event_meta, stat_meta = "", [], [], []
        for f, v in _fields(plane):
            if f == 2:
                name = v.decode("utf-8", "replace")
            elif f == 3:
                lines.append(v)
            elif f == 4:
                event_meta.append(v)
            elif f == 5:
                stat_meta.append(v)
        if not DEVICE_PLANE.match(name):
            continue
        stat_names: Dict[int, str] = {}
        for raw in stat_meta:
            key, meta = _map_entry(raw)
            for f, v in _fields(meta):
                if f == 2:
                    stat_names[key] = v.decode("utf-8", "replace")
        metadata: Dict[int, Tuple[str, str]] = {}
        for raw in event_meta:
            key, meta = _map_entry(raw)
            full, stats = "", []
            for f, v in _fields(meta):
                if f == 2:
                    full = v.decode("utf-8", "replace")
                elif f == 5:
                    stats.append(v)
            found = _stats(stats, stat_names)
            metadata[key] = (short_name(full),
                             str(found.get("tf_op") or "").rstrip(":"))
        per_line = out.setdefault(name, {})
        for raw in lines:
            line_name, base_ns, events = "", 0, []
            for f, v in _fields(raw):
                if f == 2:
                    line_name = v.decode("utf-8", "replace")
                elif f == 3:
                    base_ns = _signed(v)
                elif f == 4:
                    events.append(v)
            ops = per_line.setdefault(line_name, [])
            for ev in events:
                meta_id = offset_ps = duration_ps = 0
                for f, v in _fields(ev):
                    if f == 1:
                        meta_id = v
                    elif f == 2:
                        offset_ps = _signed(v)
                    elif f == 3:
                        duration_ps = _signed(v)
                short, op_name = metadata.get(meta_id, ("?", ""))
                ops.append(Op(short, base_ns + offset_ps / 1000.0,
                              duration_ps / 1000.0, op_name))
    return out


def read_bytes(path: Path) -> bytes:
    raw = Path(path).read_bytes()
    return gzip.decompress(raw) if raw[:2] == b"\x1f\x8b" else raw


def load_file(path: Path) -> Dict[str, Dict[str, List[Op]]]:
    return load_ops(read_bytes(path))


# -- scopes -------------------------------------------------------------------

def scope_of(op_name: str) -> str:
    """The program's scope of an operation (module docstring), or
    ``"unscoped"``. The last component goes only where it is a primitive:
    a ``while`` operation's own ``op_name`` ends in the scope it sits in."""
    raw = [p for p in op_name.split("/") if p]
    parts = [p for p in raw if not _TRANSFORM.match(p)]
    if raw and not _TRANSFORM.match(raw[-1]):
        parts = parts[:-1]
    return "/".join(parts) or UNSCOPED


def stage_of(scope: str) -> str:
    """``<family>/<stage>``, the first two components of a scope; a scope
    that names no stage is ``"unscoped"``."""
    parts = scope.split("/")
    return "/".join(parts[:2]) if len(parts) >= 2 else UNSCOPED


def stage_seconds(selfs: List[Tuple[Op, float]]) -> Dict[str, float]:
    """Self seconds per ``<family>/<stage>``."""
    out: Dict[str, float] = {}
    for scope, s in scope_seconds(selfs).items():
        stage = stage_of(scope)
        out[stage] = out.get(stage, 0.0) + s
    return out


def session_unix_ns(data: bytes) -> Optional[Tuple[int, int]]:
    """``(profile_start_time, profile_stop_time)`` of the "Task Environment"
    plane, unix nanoseconds, where the trace has them: when the profiler's
    session ran on the wall clock. An estimate of where the trace's zero
    lies, not a bound on it."""
    for field, plane in _fields(data):
        if field != 1:
            continue
        name, stats, stat_meta = "", [], []
        for f, v in _fields(plane):
            if f == 2:
                name = v.decode("utf-8", "replace")
            elif f == 6:
                stats.append(v)
            elif f == 5:
                stat_meta.append(v)
        if name != "Task Environment":
            continue
        names: Dict[int, str] = {}
        for raw in stat_meta:
            key, meta = _map_entry(raw)
            for f, v in _fields(meta):
                if f == 2:
                    names[key] = v.decode("utf-8", "replace")
        found = _stats(stats, names)
        if "profile_start_time" in found and "profile_stop_time" in found:
            return (int(found["profile_start_time"]),
                    int(found["profile_stop_time"]))
    return None


def self_times(ops: List[Op], t0: float, t1: float
               ) -> List[Tuple[Op, float]]:
    """``(op, self nanoseconds inside [t0, t1])`` of every operation of one
    line that touches the window: its duration less what the operations
    nested inside it cover (a ``while`` holds its body's operations), both
    clipped to the window."""
    out: List[Tuple[Op, float]] = []
    stack: List[List[Any]] = []  # [op, end, self]

    def inside(start: float, end: float) -> float:
        return max(0.0, min(end, t1) - max(start, t0))

    for op in sorted(ops, key=lambda o: (o.start_ns, -o.dur_ns)):
        end = op.start_ns + op.dur_ns
        if end <= t0 or op.start_ns >= t1:
            continue
        while stack and op.start_ns >= stack[-1][1]:
            done = stack.pop()
            out.append((done[0], max(done[2], 0.0)))
        if stack:
            stack[-1][2] -= inside(op.start_ns, min(end, stack[-1][1]))
        stack.append([op, end, inside(op.start_ns, end)])
    while stack:
        done = stack.pop()
        out.append((done[0], max(done[2], 0.0)))
    return out


def scope_seconds(selfs: List[Tuple[Op, float]]) -> Dict[str, float]:
    """Self seconds per scope."""
    out: Dict[str, float] = {}
    for op, ns in selfs:
        scope = scope_of(op.op_name)
        out[scope] = out.get(scope, 0.0) + ns / 1e9
    return out


def named_ops(selfs: List[Tuple[Op, float]], top: int = 10
              ) -> List[List[Any]]:
    """``[["<scope> %name kind", self seconds]]`` of the ``top`` operations
    with most self time: the breakdown's entries, each under the program's
    own name for where it runs. Keyed by scope and name, so one compiler
    name in two programs (two wire batches) stays one entry per scope."""
    total: Dict[str, float] = {}
    for op, ns in selfs:
        key = f"{scope_of(op.op_name)} {op.name}"
        total[key] = total.get(key, 0.0) + ns / 1e9
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:top]]


def modules(planes: Dict[str, Dict[str, List[Op]]]) -> List[Op]:
    """The first chip's "XLA Modules" events in time order: one per program
    run, named ``jit_<program>(<program id>)``."""
    if not planes:
        return []
    first = planes[sorted(planes)[0]]
    return sorted(first.get(MODULES_LINE, []), key=lambda o: o.start_ns)


def ops_line(planes: Dict[str, Dict[str, List[Op]]]) -> Optional[List[Op]]:
    if not planes:
        return None
    return planes[sorted(planes)[0]].get(OPS_LINE)
