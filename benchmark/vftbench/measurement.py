"""What one run measured, in the form the metric readers take it.

A reader (``benchmark/readers/<metric>.py``) is a function ``read(m)`` of one
:class:`Measurement` that returns a number or ``None``. It reads what its
metric's ``source`` says: the host's clock (``completions``, ``requests``,
``cpu_s``), the program's spans and counters (``stage_spans``, ``responses``,
``dispatches``) or the device trace (``trace``). What a run did not record is
empty or ``None``, and a reader that finds nothing returns ``None``.
"""
from __future__ import annotations

import re
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

from . import stats


class Measurement:
    def __init__(self) -> None:
        #: process start -> first instant of the measured window
        self.setup_s: Optional[float] = None
        #: the measured window on the perf_counter clock
        self.t0: float = 0.0
        self.t1: float = 0.0
        #: (visible at, units) of every good response, warm-up excluded
        self.completions: List[Tuple[float, int]] = []
        #: open loop: one dict per request with due/taken_up/visible/units
        self.requests: List[Dict[str, Any]] = []
        #: resident driver: units per second of every timing block
        self.block_rates: List[float] = []
        #: the program's response JSONs of requests answered in the window
        self.responses: List[Dict[str, Any]] = []
        #: (stage, start, duration) of every StageProfiler stage call that
        #: touched the window, every thread; traced runs only
        self.stage_spans: List[Tuple[str, float, float]] = []
        #: (at, rows, rows after padding) of every dispatch into the
        #: runner, seen by the harness's wrapper; traced runs only
        self.dispatches: List[Tuple[float, int, int]] = []
        #: user+system seconds of the process over the window
        self.cpu_s: Optional[float] = None
        #: tracing.reduce_trace()'s result for the traced sub-window
        self.trace: Optional[Dict[str, Any]] = None
        #: the profiler's file of that sub-window, and ``perf_counter`` as
        #: ``start_trace`` was called (the trace's zero lies between this
        #: and the next), as it returned and as ``stop_trace`` was called
        #: (the sub-window's edges); ``tracing.TraceWindow`` sets them
        self.trace_path: Optional[Path] = None
        self.trace_zero_perf: Optional[float] = None
        self.trace_open_perf: Optional[float] = None
        self.trace_close_perf: Optional[float] = None
        #: seconds inside the window in which the driver itself dispatched
        #: nothing (the resident driver starting and stopping the profiler
        #: between two blocks): no part of the rate that was dispatched
        self.paused_s: float = 0.0
        #: costs/<config>.py per_unit(): algorithmic FLOPs and bytes
        self.costs: Dict[str, Any] = {}
        #: the peaks table's row for this device
        self.peaks: Dict[str, Any] = {}
        #: the fullest chip's peak over the whole process, warm-up included,
        #: read after the window, and the same reading as the window opened
        self.memory_peak_bytes: int = 0
        self.memory_peak_at_open_bytes: int = 0
        #: what an unanswered request's latency counts as, and the open
        #: loop's limit on a request's latency (both from the traffic file)
        self.drain_limit_s: float = 0.0
        self.latency_limit_s: Optional[float] = None

    # -- helpers the readers share ------------------------------------------
    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def units(self) -> int:
        """Units completed inside the window."""
        return stats.units_in_window(self.completions, self.t0, self.t1)

    def stage_s(self, stage: str) -> Optional[float]:
        """Seconds of ``stage`` inside the window, summed over threads."""
        spans = [(s, d) for name, s, d in self.stage_spans if name == stage]
        if not spans:
            return None
        return stats.clipped_seconds(spans, self.t0, self.t1)

    def per_unit(self, seconds: Optional[float]) -> Optional[float]:
        units = self.units()
        if seconds is None or units <= 0:
            return None
        return seconds / units

    def latencies(self) -> List[float]:
        """Open loop: seconds from due to response, of the requests that
        were due inside the window."""
        return stats.request_latencies(
            [r["due"] for r in self.requests],
            [r.get("visible") for r in self.requests],
            self.t0, self.t1, self.drain_limit_s)

    def wire_batches(self) -> Dict[int, int]:
        """``{rows after padding: dispatches}`` inside the window."""
        out: Dict[int, int] = {}
        for at, _, padded in self.dispatches:
            if self.t0 <= at < self.t1:
                out[padded] = out.get(padded, 0) + 1
        return dict(sorted(out.items()))

    def dispatched_per_s(self) -> Optional[float]:
        """Units per second that entered the runner inside the window, over
        the seconds in which the driver dispatched: ``paused_s`` is left
        out, or the seconds ``stop_trace`` takes would read as a slower
        device (ledger, PR 25: 0.659 and 0.765 ms a clip at one rate)."""
        rows = sum(r for at, r, _ in self.dispatches if self.t0 <= at < self.t1)
        if not rows or self.window_s - self.paused_s <= 0:
            return None
        return rows / (self.window_s - self.paused_s)

    def device_s_per_unit(self) -> Optional[float]:
        """Device-busy seconds per unit: the busy share of the traced
        sub-window over the units per second dispatched in the whole window
        (:meth:`dispatched_per_s`).
        (Counting the units of the sub-window alone would swing by a whole
        dispatch: a few seconds hold few of them, and a busy device runs them
        seconds after they were dispatched.)"""
        rate = self.dispatched_per_s()
        if self.trace is None or not rate or not self.trace["window_s"]:
            return None
        return self.trace["busy_s"] / self.trace["window_s"] / rate

    def idle_share(self) -> Optional[float]:
        """Percent of the traced sub-window with no operation on the device."""
        if self.trace is None or not self.trace["window_s"]:
            return None
        return 100.0 * (1.0 - self.trace["busy_s"] / self.trace["window_s"])

    def op_share(self, pattern: "re.Pattern") -> Optional[float]:
        """Share (0..1) of the device's busy time, inside the traced
        sub-window, spent in operations whose name matches ``pattern``."""
        if self.trace is None or not self.trace["busy_s"]:
            return None
        return sum(s for name, s in self.trace["self_s"].items()
                   if pattern.search(name)) / self.trace["busy_s"]

    def roofline_s_per_unit(self) -> Optional[Tuple[float, str]]:
        """The least time the chip could take for one unit, and which peak
        sets it: ``("compute" | "memory")``."""
        if not self.costs or not self.peaks:
            return None
        compute = self.costs["flops"] / self.peaks["bf16_flops_per_s"]
        memory = self.costs["bytes"] / self.peaks["hbm_bytes_per_s"]
        return (compute, "compute") if compute >= memory else \
            (memory, "memory")


#: how a Mosaic (Pallas) kernel shows on the device's operation line
MOSAIC_OPS = re.compile(r"custom.call|mosaic|pallas", re.IGNORECASE)
