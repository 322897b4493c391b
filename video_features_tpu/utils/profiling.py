"""Per-stage timing + XLA trace capture.

The reference has no observability beyond tqdm and print (SURVEY §5:
"Tracing / profiling: absent", reference main.py:14-18 "TODO: logging").
Here profiling is a first-class subsystem:

  - :data:`profiler` — a process-global stage timer. Pipelines wrap their
    hot phases in ``with profiler.stage("decode")`` etc.; when disabled the
    context manager is a no-op (two attribute reads), so instrumentation
    stays in place permanently. Stages used by the built-in pipelines:
    ``decode`` (cv2 read + host transform), ``forward``, ``write`` (sink
    IO). Under the synchronous path ``forward`` is true H2D + forward + D2H
    wall time; under async dispatch (FeatureStream, the default) it is the
    host's *stall* time materializing results — near-zero ``forward`` means
    the chip is fully hidden behind decode (see docs/performance.md).
  - ``profile=true`` on the CLI prints the aggregate per-stage breakdown at
    the end of the run — the decode-vs-forward-vs-write split that tells
    you whether the chip or the host is the bottleneck.
  - ``profile_trace_dir=/path`` additionally captures a ``jax.profiler``
    trace (one per run) viewable in TensorBoard/Perfetto, with device-side
    op timelines.
  - ``telemetry=true`` (telemetry/) rides the SAME ``profiler.stage`` call
    sites: the recorder installs :meth:`StageProfiler.set_hook`, which
    feeds latency histograms and per-video spans without new code in the
    hot loops. Stages are timed whenever either consumer (aggregate
    printing or the hook) is active.
"""
from __future__ import annotations

import time
from collections import defaultdict
from typing import Any, Callable, Dict, Optional, Tuple


class _NoopStage:
    """What :meth:`StageProfiler.stage` returns when nothing listens: one
    shared, state-free ``with`` (no generator, no clock read)."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


NOOP_STAGE = _NoopStage()


class _Stage:
    """One armed ``with profiler.stage(name)``: listeners are read at entry
    (one installed mid-stage does not see a stage it did not see start)."""

    __slots__ = ("_p", "_name", "_t0", "_hook", "_trace_hook", "_span")

    def __init__(self, p: "StageProfiler", name: str) -> None:
        self._p = p
        self._name = name

    def __enter__(self) -> None:
        p = self._p
        self._hook = p._hook
        self._trace_hook = p._trace_hook
        tracer = p._tracer
        # the timeline's span opens first and closes last, so that spans
        # opened inside the stage (decode.read under decode) are its children
        self._span = tracer(self._name) if tracer is not None else None
        if self._span is not None:
            self._span.__enter__()
        self._t0 = time.perf_counter()
        return None

    def __exit__(self, exc_type, exc, tb) -> bool:
        p, name, t0 = self._p, self._name, self._t0
        dt = time.perf_counter() - t0
        if self._span is not None:
            try:
                self._span.__exit__(exc_type, exc, tb)
            except Exception:
                pass  # observability must never fail the pipeline
        if p.enabled:
            with p._lock:
                p._times[name] += dt
                p._counts[name] += 1
        if self._hook is not None:
            try:
                self._hook(name, dt)
            except Exception:
                pass
        if self._trace_hook is not None:
            try:
                self._trace_hook(name, t0, dt)
            except Exception:
                pass
        return False


class StageProfiler:
    """Accumulates wall time and call counts per named stage."""

    def __init__(self) -> None:
        import threading
        self.enabled = False
        self._lock = threading.Lock()  # decode runs in the Prefetcher thread
        self._times: Dict[str, float] = defaultdict(float)
        self._counts: Dict[str, int] = defaultdict(int)
        self._hook: Optional[Callable[[str, float], None]] = None
        self._trace_hook: Optional[Callable[[str, float, float],
                                            None]] = None
        self._tracer: Optional[Callable[[str], Any]] = None

    def set_hook(self, hook: Optional[Callable[[str, float], None]]) -> None:
        """Install (or clear, with None) a per-observation callback
        ``hook(stage_name, seconds)`` — the telemetry recorder's feed.
        Timing happens whenever ``enabled`` OR a hook is present."""
        self._hook = hook

    def set_trace_hook(self, hook: Optional[Callable[[str, float, float],
                                                     None]]) -> None:
        """Install (or clear) ``hook(stage_name, t0_perf, seconds)``: a
        listener to the stage timeline that wants the START time too (the
        benchmark harness's feed). It is a slot of its own: the trace
        recorder (telemetry/trace.py) feeds through :meth:`set_span_tracer`,
        so the two never unhook each other. A listener is also a
        subscription to the program's whole span tree: while one is
        installed and no ``trace=true`` recorder runs, telemetry/trace.py
        records to memory (``trace.last_recording()`` hands it over)."""
        self._trace_hook = hook
        if self is profiler:  # the process's timeline, not a private timer
            from ..telemetry import trace
            trace.follow_stage_listener(hook is not None)

    def set_span_tracer(self, tracer: Optional[Callable[[str], Any]]) -> None:
        """Install (or clear) ``tracer(stage_name) -> context manager``:
        the trace recorder opens one span per stage call through it, so a
        stage is a node of the span tree (parent of what runs inside it)."""
        self._tracer = tracer

    def stage(self, name: str):
        if not self.enabled and self._hook is None \
                and self._trace_hook is None and self._tracer is None:
            return NOOP_STAGE
        return _Stage(self, name)

    def add(self, name: str, dt: float, n: int = 1) -> None:
        """Accumulate an externally-timed observation (the telemetry
        recorder's delta/total accumulators use this; ``enabled`` gates
        only the context-manager path)."""
        with self._lock:
            self._times[name] += dt
            self._counts[name] += n

    def snapshot(self) -> Dict[str, Tuple[float, int]]:
        with self._lock:
            return {k: (self._times[k], self._counts[k])
                    for k in self._times}

    def reset(self) -> None:
        with self._lock:
            self._times.clear()
            self._counts.clear()

    def drain(self) -> Dict[str, Tuple[float, int]]:
        """Atomic snapshot+reset under ONE lock acquisition.

        The old ``snapshot()``-then-``reset()`` pair could lose a stage
        update landing between the two calls (each took the lock
        independently); flushers that turn accumulated stage time into
        per-interval deltas (telemetry/recorder.py heartbeats) must use
        this instead."""
        with self._lock:
            out = {k: (self._times[k], self._counts[k])
                   for k in self._times}
            self._times.clear()
            self._counts.clear()
            return out

    def summary(self, title: str = "profile") -> str:
        """Stages can overlap in wall time (decode runs in the Prefetcher
        thread while forward runs on the main thread), so the accounted
        total can exceed wall clock — that overlap is the pipeline working
        as designed."""
        snap = self.snapshot()
        if not snap:
            return f"[{title}] no stages recorded"
        total = sum(t for t, _ in snap.values())
        lines = [f"[{title}] total accounted: {total:.3f}s"]
        for name, (t, n) in sorted(snap.items(), key=lambda kv: -kv[1][0]):
            lines.append(
                f"  {name:<10} {t:8.3f}s  {100 * t / total:5.1f}%  "
                f"{n:6d} calls  {1e3 * t / max(n, 1):8.3f} ms/call")
        return "\n".join(lines)


profiler = StageProfiler()


class TraceCapture:
    """``jax.profiler`` trace over a region, no-op when dir is None.

    Only the device is traced. With the profiler's default options the
    host and Python tracers run too, and on the v5e the runtime's own
    threads then write ~2 M events a second (590 MB for eight seconds of
    RAFT) while the device sits idle 21-26% where it is idle 0.3% untraced
    (PERF.md section 6, PR 22): such a trace measures the tracer. The host
    side of the timeline is ``trace=true`` (telemetry/trace.py)."""

    def __init__(self, trace_dir: Optional[str]) -> None:
        self.trace_dir = trace_dir
        self._active = False

    def __enter__(self):
        if self.trace_dir:
            import jax
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 0
            options.enable_hlo_proto = False
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=options)
            self._active = True
        return self

    def __exit__(self, *exc):
        if self._active:
            import jax
            jax.profiler.stop_trace()
            self._active = False
        return False
