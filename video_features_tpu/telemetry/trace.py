"""Pipeline tracing: a Chrome-trace-event timeline of the host pipeline.

The stage *sums* the rest of the telemetry subsystem records
(``_telemetry.jsonl`` spans, heartbeat ``stage_delta``) can say decode
took 2x less total time while end-to-end stayed ~1x — but not WHY:
which FrameBus queue blocked, which family starved, where the critical
path ran. This module answers that with a timeline: every
``profiler.stage`` call site, every fan-out backpressure stall, every
retry backoff becomes one event in ``{output_path}/_trace.json``,
written in the Chrome trace-event format that Perfetto
(https://ui.perfetto.dev), ``chrome://tracing`` and TensorBoard all
consume — the same format ``jax.profiler`` emits for the device
timeline, so ``scripts/trace_report.py --merge`` can splice host and
device into one view.

Design constraints, in order:

  1. **zero hot-path cost when off** (the default): the module-level
     helpers read ONE global; :func:`span` returns a shared no-op
     context manager, exactly the ``NOOP_SPAN`` discipline of
     telemetry/spans.py, and ``profiler.stage`` does the same when
     nothing listens.
  2. **low overhead when on**: events append to per-THREAD buffers
     (no lock on the hot path — each buffer is owned by exactly one
     writer thread; the recorder lock is taken once per thread at
     buffer creation and once at drain);
  3. **bounded**: per-thread buffers cap at
     :data:`MAX_EVENTS_PER_THREAD`; overflow is counted and surfaced
     in the file's ``otherData``, never silently lost or unbounded;
  4. **crash-consistent**: the file materializes only at
     :meth:`TraceRecorder.close` via the same temp+fsync+``os.replace``
     discipline as every other telemetry artifact (telemetry/jsonl.py)
     — a reader can see a complete trace or no trace, never a torn one.
     ``scripts/trace_report.py`` still fails with a CLEAR message (not
     a JSON traceback) on a file torn by pre-PR writers or disk faults.

**One tree per request** (PR 24). A complete event is a node: ``sid``
its id, ``parent`` the enclosing open span of its thread (for the top
span of a decode-ahead thread, the consumer's span it was started under,
:class:`adopt`), ``rid`` the request in scope (telemetry/context.py) and
``cpu`` the thread's CPU time over the span. ``profiler.stage`` call
sites are nodes too (the recorder is the profiler's span tracer), so
``decode.read`` hangs under ``decode`` under ``prefetch.next`` under the
worker's ``video_attempt`` under ``serve.request``. The hot path is
tiled: what of a request no span covers is a metric
(``serve.unnamed_share``, benchmark/readers). A listener to the stage
timeline (``profiler.set_trace_hook``: the benchmark's traced run) gets
the tree too, recorded to memory: :func:`follow_stage_listener`.

Enabled by ``trace=true`` on the CLI (cli.py owns the recorder
lifecycle, like ``telemetry=true``); composes with — but does not
require — ``telemetry=true``. Event vocabulary and the per-``ph``
required fields are pinned by :data:`REQUIRED_X_FIELDS` /
:data:`KNOWN_SPAN_NAMES`, which ``scripts/check_trace_schema.py``
validates against a real smoke run so emitter and checker cannot
drift (docs/observability.md "Reading the pipeline timeline").
"""
from __future__ import annotations

import os
import socket
import threading
import time
from typing import Any, Dict, List, Optional

from ..utils.profiling import profiler
from . import jsonl
from .context import current_request_id

TRACE_FILENAME = "_trace.json"

#: stitched/merged outputs share the ``_trace`` prefix but are never
#: inputs: trace discovery (trace_report, vft-fleet --stitch) skips them
TRACE_OUTPUT_NAMES = ("_trace_fleet.json", "_trace_merged.json")


def trace_filename(host_id: Optional[str] = None) -> str:
    """The trace artifact name: ``_trace.json`` for a single-writer
    output dir, ``_trace_{host_id}.json`` when N hosts co-own one dir
    (fleet=queue workers, vft-serve siblings on a spool) — otherwise the
    last host to close would silently overwrite every other host's
    timeline, and ``vft-fleet --stitch`` could never show the fleet.
    Sanitation matches telemetry/heartbeat.py heartbeat_filename."""
    if host_id is None:
        return TRACE_FILENAME
    import re
    safe = re.sub(r"[^A-Za-z0-9._-]+", "-", str(host_id))
    return f"_trace_{safe}.json"

#: trace format identifier stamped into ``otherData``
TRACE_SCHEMA = "vft.trace/1"

#: required keys per event phase — scripts/check_trace_schema.py
#: validates every emitted event against exactly these, so the emitter
#: and the CI gate cannot drift
REQUIRED_X_FIELDS = ("ph", "ts", "dur", "pid", "tid", "name")
#: what every complete event carries beside the above (added fields, so
#: the schema keeps its name and an older trace still reads):
#: ``sid`` this span's id (``<thread buffer>.<n>``: minted without a lock),
#: ``parent`` the sid of the enclosing open span of its thread (for the top
#: span of a decode-ahead thread, the consumer's span it was started
#: under; null at a root), ``rid`` the request in scope
#: (telemetry/context.py; null outside serve mode) and ``cpu`` the
#: thread's CPU microseconds over the span (``time.thread_time()``; null
#: for an externally-timed :func:`complete`). Top-level keys, not ``args``:
#: the tree is the recorder's, the args are the call site's.
SPAN_TREE_FIELDS = ("sid", "parent", "rid", "cpu")
REQUIRED_I_FIELDS = ("ph", "ts", "pid", "tid", "name")
REQUIRED_C_FIELDS = ("ph", "ts", "pid", "name", "args")
REQUIRED_M_FIELDS = ("ph", "pid", "name", "args")

#: the StageProfiler stages, which reach the timeline under their own names
STAGE_NAMES = ("decode", "h2d", "forward", "write")

#: the span vocabulary the instrumentation emits (beyond the
#: profiler.stage names, which arrive verbatim: decode/h2d/forward/write).
#: scripts/trace_report.py's stall ranking and critical-path verdict
#: key off these names — keep the three lists in sync.
KNOWN_SPAN_NAMES = (
    "video_attempt",        # one safe_extract attempt (args: video, attempt)
    "family",               # one family's whole per-video job (multi runs)
    "fanout.decode_pass",   # the FrameBus union decode pass, whole video
    "fanout.put_blocked",   # decoder blocked: a family's queue was full
    "fanout.get_starved",   # family blocked: waiting on the decoder
    "fanout.subscribe_wait",  # family blocked at the arrival barrier
    "prefetch.next",        # decode-ahead producer pulling one batch
    "prefetch.put_blocked",  # producer blocked: consumer fell behind
    "retry_backoff",        # fault-runtime sleep between attempts
    "wav_rip",              # ffmpeg audio rip (shared or private)
    "source_probe",         # private VideoSource construction/probing
    "fleet.claim",          # work-queue claim attempt (parallel/queue.py)
    "fleet.steal",          # instant: claimed a reclaimed item
    "fleet.reclaim",        # instant: expired lease pushed back to pending
    "fleet.idle_wait",      # queue empty, other hosts hold live leases
    "fleet.canary",         # joining-host canary re-extraction
    "cache.lookup",         # feature-cache probe before extraction (cache.py)
    "cache.store",          # feature-cache write after it
    # -- the hot path from claim to response (PR 24); who reads each is in
    # docs/observability.md "Inventory" and PERF.md section 3
    "serve.request",        # one request's videos (serve.py; args: id)
    "serve.claim",          # part=rename (loop thread) / part=read (worker)
    "serve.respond",        # the done/ response write
    "decode.read",          # cv2 read of one frame (child of `decode`)
    "decode.skip",          # grab()-skip of a frame the fps filter drops
    "decode.transform",     # the host transform of one frame
    "decode.resize",        # ... its resize (ops/host_transforms.py)
    "decode.ingest",        # ... its crop + wire encoding (I420, uint8)
    "prefetch.get_wait",    # consumer waiting for the decode-ahead thread
    "batch.assemble",       # an extractor's np.stack into a clip / batch
    "batch.collect",        # after the D2H: transposes, per-video concat
    "packer.fill_wait",     # close_video waiting for others to fill a group
    "packer.lock_wait",     # at the dispatch / drain lock (args: lock)
    "packer.stack",         # the group's np.stack
    "packer.route",         # rows of a drained group back to their videos
    "mesh.pad",             # pad to the wire bucket (args: seq, rows)
    "mesh.enqueue",         # the jitted call (seq, rows, padded_rows, program)
    "mesh.fetch",           # the blocking D2H of dispatch `seq` (in forward)
    # -- the start-up ledger's phases (PR 36, telemetry/startup.py), on the
    # timeline where a recorder already runs; `startup.ready` is an instant
    "startup.backend",      # the first touch of the backend (args: device)
    "startup.cache_attach",  # compile_cache.py: verify + activate an entry
    "startup.params",       # resolve_params / init_params (model_key, kinds)
    "startup.place",        # host side of the parameters' device_put
    "startup.first_dispatch",  # first enqueue of a padded shape (program)
)

#: the counter tracks the hot path emits (``trace.counter``)
KNOWN_COUNTER_NAMES = (
    "packer.buffered",      # clips in the shared buffer after an add/flush
    "packer.ragged_flush",  # rows of the one ragged dispatch, when it fires
    "packer.row_fill",      # a sealed token row: series tokens / capacity
    "packer.pair_fill",     # ... its causal same-document pairs / T squared
    "attention.blocks",     # ... the block pairs attention folds: kept / total
    "stream.inflight",      # un-materialized outputs of a FeatureStream
)

#: stall names ranked by scripts/trace_report.py "top stalls" —
#: fleet.idle_wait is the per-host idle TAIL (this worker out of work
#: while a straggler finishes), the makespan cost work-stealing shrinks
STALL_SPAN_NAMES = ("fanout.put_blocked", "fanout.get_starved",
                    "fanout.subscribe_wait", "prefetch.put_blocked",
                    "retry_backoff", "fleet.idle_wait",
                    "prefetch.get_wait", "packer.fill_wait",
                    "packer.lock_wait")

#: stalls shorter than this never become trace events (they still
#: accumulate into the telemetry counters): a healthy pipeline performs
#: thousands of sub-millisecond queue waits per video, and recording
#: each would cost more than the stall it observes
STALL_MIN_S = 0.001

#: per-thread event cap: first N kept, overflow counted in ``otherData``
MAX_EVENTS_PER_THREAD = 500_000

#: the active run's TraceRecorder, or None (tracing disabled)
_active: Optional["TraceRecorder"] = None


def _set_active(recorder: Optional["TraceRecorder"]) -> None:
    global _active
    _active = recorder


def active() -> Optional["TraceRecorder"]:
    """The active :class:`TraceRecorder`, if any (one global read).

    Hot per-frame call sites hold the result in a local and skip even
    the kwargs construction when it is None."""
    return _active


class _NoopTraceSpan:
    """``trace=false`` hot path: a single shared, state-free ``with``."""

    __slots__ = ()

    def __enter__(self) -> "_NoopTraceSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        return None


NOOP_TRACE_SPAN = _NoopTraceSpan()


# -- module-level helpers (no-ops when tracing is off) -----------------------

def span(name: str, **args: Any):
    """Context manager timing a block into one complete ('X') event."""
    r = _active
    if r is None:
        return NOOP_TRACE_SPAN
    return _TraceSpan(r, name, args)


def complete(name: str, t0: float, dur_s: float, **args: Any) -> None:
    """Record an externally-timed block (``t0`` from
    ``time.perf_counter()``) as one complete event."""
    r = _active
    if r is not None:
        r.complete(name, t0, dur_s, **args)


def instant(name: str, **args: Any) -> None:
    """Record a point-in-time marker."""
    r = _active
    if r is not None:
        r.instant(name, **args)


def counter(name: str, value: float, series: str = "value") -> None:
    """Record one sample of a counter track (rendered as a graph lane)."""
    r = _active
    if r is not None:
        r.counter(name, value, series)


def current_span_id() -> Optional[str]:
    """The ``sid`` of the innermost span open on THIS thread, if tracing is
    on and one is open: what a helper thread is :func:`adopt`-ed under."""
    r = _active
    if r is None:
        return None
    b = r._buf()
    return b.stack[-1] if b.stack else b.adopted


class adopt:
    """``with adopt(parent_sid):`` on a helper thread (the decode-ahead
    producer): its top-level spans name ``parent_sid`` as their parent, so
    the tree of one request spans its threads. No-op when tracing is off or
    there is no parent. The request id travels separately (``use_request``)."""

    __slots__ = ("_parent", "_b", "_prev")

    def __init__(self, parent: Optional[str]) -> None:
        self._parent = parent
        self._b = None

    def __enter__(self) -> "adopt":
        r = _active
        if r is not None and self._parent is not None:
            self._b = r._buf()
            self._prev = self._b.adopted
            self._b.adopted = self._parent
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._b is not None:
            self._b.adopted = self._prev
        return None


class _TraceSpan:
    """The armed ``with`` returned by :func:`span`: a node of its thread's
    span tree. Opens on entry (so spans inside it know their parent), times
    wall and thread CPU, and emits on exit (exceptional exits included — a
    failed attempt is exactly the kind of span an operator wants on the
    timeline)."""

    __slots__ = ("_r", "_name", "_args", "_t0", "_c0", "_b", "_sid",
                 "_parent", "_rid")

    def __init__(self, recorder: "TraceRecorder", name: str,
                 args: Dict[str, Any]) -> None:
        self._r = recorder
        self._name = name
        self._args = args
        self._t0 = 0.0

    def __enter__(self) -> "_TraceSpan":
        b = self._b = self._r._buf()
        b.n += 1
        self._sid = f"{b.index}.{b.n}"
        stack = b.stack
        self._parent = stack[-1] if stack else b.adopted
        stack.append(self._sid)
        self._rid = current_request_id()
        self._c0 = time.thread_time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        dur = time.perf_counter() - self._t0
        cpu = time.thread_time() - self._c0
        stack = self._b.stack
        if stack and stack[-1] == self._sid:
            stack.pop()
        elif self._sid in stack:
            # a generator that yields inside a stage leaves that span open
            # when the span around its next() closes: close what is above
            del stack[stack.index(self._sid):]
        self._r._emit_span(self._b, self._name, self._t0, dur, cpu,
                           self._sid, self._parent, self._rid, self._args)
        return None


class _ThreadBuf:
    __slots__ = ("events", "dropped", "tid", "tname", "index", "n", "stack",
                 "adopted")

    def __init__(self, tid: int, tname: str, index: int) -> None:
        self.events: List[dict] = []
        self.dropped = 0
        self.tid = tid
        self.tname = tname
        #: unique per buffer (thread idents are reused once a thread ends,
        #: and a decode-ahead thread lives for one video)
        self.index = index
        self.n = 0                       # spans minted on this thread
        self.stack: List[str] = []       # sids of the spans open now
        self.adopted: Optional[str] = None  # parent of a top-level span


class TraceRecorder:
    """Run-scoped trace collection: construct, :meth:`start`, let the
    instrumentation points feed it, :meth:`close` in a ``finally``.

    Also installs itself as the :class:`StageProfiler` span tracer, so
    every ``profiler.stage("decode"|"h2d"|"forward"|"write")`` call site is
    a node of the span tree with zero new code in the hot loops. That slot
    is not the ``set_trace_hook`` one: a stage listener installed before or
    after :meth:`start` keeps getting every stage.

    ``output_path=None`` records to memory only: nothing is written at
    :meth:`close` and :meth:`events` hands the timeline over (the benchmark
    harness's traced run; see :func:`follow_stage_listener`).
    """

    def __init__(self, output_path: Optional[str], *,
                 pid: Optional[int] = None,
                 host_id: Optional[str] = None,
                 max_events_per_thread: int = MAX_EVENTS_PER_THREAD) -> None:
        self.output_path = None if output_path is None else str(output_path)
        self.host_id = host_id
        self.trace_path = None if output_path is None else os.path.join(
            self.output_path, trace_filename(host_id))
        self.pid = os.getpid() if pid is None else int(pid)
        self.max_events_per_thread = int(max_events_per_thread)
        #: ``time.perf_counter()`` at ts 0: event time = perf0 + ts / 1e6
        self.perf0 = time.perf_counter()
        #: the same instant on the wall clock (``time.time()``)
        self.start_unix = time.time()
        self._lock = threading.Lock()
        self._bufs: List[_ThreadBuf] = []
        self._tls = threading.local()
        self._closed = False

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "TraceRecorder":
        if self.output_path is not None:
            os.makedirs(self.output_path, exist_ok=True)
        _set_active(self)
        profiler.set_span_tracer(self.span)
        return self

    def close(self) -> Optional[str]:
        """Uninstall the hooks and drain every thread buffer into
        ``_trace.json`` (atomic temp+rename — complete or absent, never
        torn). Idempotent; never raises into the caller's finally.
        Returns the written path, or None."""
        if self._closed:
            return None
        self._closed = True
        if profiler._tracer == self.span:
            profiler.set_span_tracer(None)
        if _active is self:
            _set_active(None)
        global _last
        _last = self
        if self.trace_path is None:
            return None
        try:
            jsonl.write_json_atomic(self.trace_path, self.build_trace(),
                                    indent=None)
            return self.trace_path
        except Exception as e:
            # ENOSPC discipline: a failed trace drain is the loss of one
            # diagnostic artifact, never a crashed run — named once, and
            # counted on the active recorder when there is one
            from . import inc
            inc("vft_telemetry_write_failures_total", pillar="trace")
            print(f"trace: failed to write {self.trace_path}: "
                  f"{type(e).__name__}: {e}")
            return None

    # -- event emission (any thread) ----------------------------------------
    def _buf(self) -> _ThreadBuf:
        b = getattr(self._tls, "buf", None)
        if b is None:
            with self._lock:
                b = _ThreadBuf(threading.get_ident(),
                               threading.current_thread().name,
                               len(self._bufs))
                self._bufs.append(b)
            self._tls.buf = b
        return b

    def _ts_us(self, perf_t: float) -> float:
        return round((perf_t - self.perf0) * 1e6, 3)

    def _emit(self, ev: dict, b: Optional[_ThreadBuf] = None) -> None:
        if self._closed:
            return  # a straggler thread after drain: drop, never corrupt
        if b is None:
            b = self._buf()
        if len(b.events) >= self.max_events_per_thread:
            b.dropped += 1
            return
        b.events.append(ev)

    def _emit_span(self, b: _ThreadBuf, name: str, t0: float, dur_s: float,
                   cpu_s: Optional[float], sid: str, parent: Optional[str],
                   rid: Optional[str], args: Dict[str, Any]) -> None:
        ev = {"ph": "X", "name": str(name), "ts": self._ts_us(t0),
              "dur": round(dur_s * 1e6, 3), "pid": self.pid,
              "tid": b.tid, "cat": "host", "sid": sid, "parent": parent,
              "rid": rid,
              "cpu": None if cpu_s is None else round(cpu_s * 1e6, 3)}
        if args:
            ev["args"] = args
        self._emit(ev, b)

    def span(self, name: str, **args: Any) -> _TraceSpan:
        return _TraceSpan(self, name, args)

    def complete(self, name: str, t0: float, dur_s: float,
                 **args: Any) -> None:
        """An externally-timed block: a leaf under whatever is open on this
        thread now (its CPU time is not known)."""
        b = self._buf()
        b.n += 1
        self._emit_span(b, name, t0, dur_s, None, f"{b.index}.{b.n}",
                        b.stack[-1] if b.stack else b.adopted,
                        current_request_id(), args)

    def instant(self, name: str, **args: Any) -> None:
        ev = {"ph": "i", "name": str(name),
              "ts": self._ts_us(time.perf_counter()), "pid": self.pid,
              "tid": threading.get_ident(), "cat": "host", "s": "t"}
        if args:
            ev["args"] = args
        self._emit(ev)

    def counter(self, name: str, value: float,
                series: str = "value") -> None:
        self._emit({"ph": "C", "name": str(name),
                    "ts": self._ts_us(time.perf_counter()), "pid": self.pid,
                    "tid": threading.get_ident(), "cat": "host",
                    "args": {series: value}})

    # -- drain --------------------------------------------------------------
    def events(self) -> List[dict]:
        """Every event recorded so far, all threads, sorted by ``ts``."""
        with self._lock:
            bufs = list(self._bufs)
        events: List[dict] = []
        for b in bufs:
            events.extend(b.events[:])
        events.sort(key=lambda e: e.get("ts", -1.0))
        return events

    def build_trace(self) -> dict:
        with self._lock:
            bufs = list(self._bufs)
        events = self.events()
        dropped = sum(b.dropped for b in bufs)
        meta: List[dict] = [{
            "ph": "M", "name": "process_name", "pid": self.pid,
            "args": {"name": f"vft-host {socket.gethostname()}"}}]
        for b in bufs:
            meta.append({"ph": "M", "name": "thread_name", "pid": self.pid,
                         "tid": b.tid, "args": {"name": b.tname}})
        return {
            "traceEvents": meta + events,
            "displayTimeUnit": "ms",
            "otherData": {
                "schema": TRACE_SCHEMA,
                "host": socket.gethostname(),
                "host_id": self.host_id,
                "pid": self.pid,
                # the wall-clock anchor: event time = start_unix + ts/1e6.
                # trace_report --merge and vft-fleet --stitch align
                # timelines from different hosts/runs on it
                "start_unix": round(self.start_unix, 3),
                # the same instant on time.perf_counter(), the clock every
                # span was read on (and the benchmark harness's)
                "perf0": self.perf0,
                "wall_s": round(time.perf_counter() - self.perf0, 3),
                "events": len(events),
                "dropped_events": dropped,
            },
        }


# -- the stage listener's memory-only recording ------------------------------

#: the last recorder that closed (memory-only ones keep their events)
_last: Optional[TraceRecorder] = None
#: the memory-only recorder a stage listener's subscription started
_implicit: Optional[TraceRecorder] = None


def follow_stage_listener(listening: bool) -> None:
    """``StageProfiler.set_trace_hook`` calls this. Whoever listens to the
    stage timeline (the benchmark harness's traced run) gets the whole span
    tree: while a listener is installed and no ``trace=true`` recorder is
    running, a memory-only :class:`TraceRecorder` records, and
    :func:`last_recording` hands it over once the listener is gone."""
    global _implicit
    if listening:
        if _active is None:
            _implicit = TraceRecorder(None).start()
    elif _implicit is not None:
        rec, _implicit = _implicit, None
        rec.close()


def last_recording() -> Optional[TraceRecorder]:
    """The running recorder, else the last one that closed: its
    :meth:`~TraceRecorder.events` and ``perf0`` are the program's timeline
    of the stretch it covered."""
    return _active if _active is not None else _last
