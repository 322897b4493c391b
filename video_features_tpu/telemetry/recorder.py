"""TelemetryRecorder: the run-scoped owner of every telemetry channel.

One recorder per CLI run (cli.py constructs it when ``telemetry=true``):

  - owns the :class:`~.metrics.MetricsRegistry` and installs the stage
    hook on the process-global ``profiler`` (utils/profiling.py), so the
    decode/forward/write context managers that already instrument the
    pipelines feed latency histograms + per-video spans with no new call
    sites in the hot loops;
  - mints :class:`~.spans.VideoSpan`\\ s and appends their records to
    ``{output_path}/_telemetry.jsonl``;
  - runs the heartbeat thread (telemetry/heartbeat.py) and writes this
    host's ``_heartbeat_{host_id}.json``, including the per-interval
    stage delta obtained from ``StageProfiler.drain()`` — the atomic
    snapshot+reset that replaces the racy snapshot-then-reset pair;
  - counts XLA compile-cache hits/misses via ``jax.monitoring`` event
    listeners (installed once per process; recorders read deltas);
  - writes the run manifest (telemetry/manifest.py) at :meth:`close`.

When no recorder is active every instrumentation point in the codebase
is a constant-time no-op: the module-level helpers in
``telemetry/__init__.py`` read one global, the profiler hook is None,
and cli.py hands out ``NOOP_SPAN``.
"""
from __future__ import annotations

import os
import socket
import threading
import time
import uuid
from typing import Callable, Dict, List, Optional

from ..utils.profiling import StageProfiler, profiler
from . import jsonl, manifest, startup
from .heartbeat import HeartbeatThread, heartbeat_filename
from .metrics import FPS_BUCKETS, LATENCY_BUCKETS, MetricsRegistry
from .spans import VideoSpan, current_span

SPANS_FILENAME = "_telemetry.jsonl"

# -- process-wide compile-cache event counts --------------------------------
# jax.monitoring listeners cannot be unregistered individually, so they are
# installed once (telemetry/startup.py, which also keeps what they say of
# every program) and recorders read deltas against a start-of-run baseline.

_install_monitoring = startup.install
_mon_snapshot = startup.cache_event_counts


def compile_cache_baseline() -> Dict[str, int]:
    """Install the ``jax.monitoring`` listeners (once per process) and
    return the counters as they stand. The drivers take this before the
    extractor is built and hand it to the recorder, so the manifest's
    hit/miss counts cover the init-time compiles too — a second process
    that recompiled its ``model.init`` must not report zero misses."""
    _install_monitoring()
    return _mon_snapshot()


def compile_cache_summary(baseline: Dict[str, int]) -> Dict[str, int]:
    """Delta of compile-cache events since ``baseline``, folded into
    hit/miss totals plus the raw per-event counts."""
    now = _mon_snapshot()
    delta = {k: now.get(k, 0) - baseline.get(k, 0) for k in now
             if now.get(k, 0) != baseline.get(k, 0)}
    out: Dict[str, int] = {"hits": 0, "misses": 0}
    for event, n in delta.items():
        if event.endswith("cache_hits"):
            out["hits"] += n
        elif event.endswith("cache_misses"):
            out["misses"] += n
        out[event] = n
    return out


class TelemetryRecorder:
    """Run-scoped telemetry: construct, :meth:`start`, hand out spans,
    :meth:`close` in a ``finally``."""

    def __init__(self, output_path: str, *,
                 run_config: Optional[dict] = None,
                 feature_type: Optional[str] = None,
                 interval_s: float = 30.0,
                 host_id: Optional[str] = None,
                 mon_baseline: Optional[Dict[str, int]] = None) -> None:
        self.output_path = str(output_path)
        self.run_config = run_config
        self.feature_type = feature_type
        self.interval_s = float(interval_s)
        self.host_id = host_id or socket.gethostname()
        # run identity: stamped into the manifest AND every heartbeat so
        # report tools can tell THIS run's heartbeats from stale files a
        # prior run left in the same output_path (telemetry_report.py
        # marks + excludes other-run heartbeats instead of summing them)
        self.run_id = uuid.uuid4().hex[:12]
        self.registry = MetricsRegistry()
        self.spans_path = os.path.join(self.output_path, SPANS_FILENAME)
        self.heartbeat_path = os.path.join(
            self.output_path, heartbeat_filename(self.host_id))
        self.manifest_path = os.path.join(
            self.output_path, manifest.MANIFEST_FILENAME)
        # run-long stage totals (manifest) + per-interval delta (heartbeat,
        # drained atomically each tick)
        self._run_stages = StageProfiler()
        self._delta_stages = StageProfiler()
        self._hb = HeartbeatThread(self._tick, self.interval_s)
        self._state_lock = threading.Lock()
        self._last_video: Optional[str] = None
        self._status_counts: Dict[str, int] = {}
        # output-health roll-up (telemetry/health.py digest_features feeds
        # it): per-family record / NaN / Inf totals for the manifest
        self._health: Dict[str, Dict[str, int]] = {}
        self._t0 = time.perf_counter()
        self._start_time = time.time()
        #: compile-cache counters at the start of what this run accounts
        #: for (compile_cache_baseline()); None -> taken at start()
        self._mon_baseline = mon_baseline
        self._started = False
        self._closed = False
        # extension hook: {section_name: zero-arg callable -> JSONable}.
        # serve.py publishes its readiness/queue state through this — the
        # heartbeat file IS the serve liveness protocol, so the recorder
        # stays the single writer (one atomic replace per tick)
        self.extra_sections: Dict[str, Callable[[], dict]] = {}
        # post-write hooks: called with the heartbeat dict just written
        # (telemetry/history.py appends its retained sample here,
        # telemetry/alerts.py evaluates its rules) — register them
        # BEFORE start() so the t=0 heartbeat is observed too, which is
        # what gives short runs a windowed baseline at all
        self.tick_hooks: List[Callable[[dict], None]] = []
        self._tick_hook_errors = 0
        # span-channel degradation latch (ENOSPC discipline): a failed
        # _telemetry.jsonl append disables the pillar for the run
        self._spans_disabled = False

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "TelemetryRecorder":
        from . import _set_active
        _install_monitoring()
        if self._mon_baseline is None:
            self._mon_baseline = _mon_snapshot()
        os.makedirs(self.output_path, exist_ok=True)
        _set_active(self)
        profiler.set_hook(self._observe_stage)
        self.write_heartbeat()  # liveness visible before the first video
        self._hb.start()
        self._started = True
        return self

    def close(self, *, tally: Optional[Dict[str, int]] = None,
              wall_s: Optional[float] = None,
              failure_tallies: Optional[Dict[str, int]] = None,
              roofline: Optional[dict] = None) -> None:
        """Stop the heartbeat thread, write a final heartbeat and the run
        manifest. Idempotent; never raises into the caller's finally.
        ``roofline`` is the run's final MFU-accounting summary
        (telemetry/roofline.py), passed explicitly by the driver so a
        later in-process run can never inherit a stale one."""
        if self._closed:
            return
        self._closed = True
        from . import _set_active
        self._hb.stop()
        profiler.set_hook(None)
        _set_active(None)
        try:
            self.write_heartbeat(final=True)
            jsonl.write_json_atomic(self.manifest_path, self.build_manifest(
                tally=tally, wall_s=wall_s, failure_tallies=failure_tallies,
                roofline=roofline))
        except Exception as e:
            print(f"telemetry: failed to write {self.manifest_path}: "
                  f"{type(e).__name__}: {e}")

    # -- spans --------------------------------------------------------------
    def video_span(self, video: str,
                   feature_type: Optional[str] = None) -> VideoSpan:
        # multi-family runs share one recorder but stamp each span with
        # its own family, so per-(video, family) records stay queryable
        return VideoSpan(video, recorder=self,
                         feature_type=feature_type or self.feature_type,
                         host_id=self.host_id)

    def emit_span(self, record: dict) -> None:
        if not self._spans_disabled:
            try:
                jsonl.append_jsonl(self.spans_path, record)
            except OSError as e:
                # a full/readonly disk (ENOSPC) must degrade this pillar,
                # not kill the extraction: drop the span channel for the
                # rest of the run, keep the in-memory counters flowing
                self._spans_disabled = True
                self.registry.counter("vft_telemetry_write_failures_total",
                                      pillar="spans").inc()
                print(f"telemetry: failed to append {self.spans_path} "
                      f"({type(e).__name__}: {e}) — span channel disabled "
                      "for this run")
        status = record.get("status", "?")
        self.registry.counter("vft_videos_total", status=status).inc()
        self.registry.histogram("vft_video_wall_seconds",
                                buckets=LATENCY_BUCKETS).observe(
                                    record.get("wall_s") or 0.0)
        frames, wall = record.get("video_frames"), record.get("wall_s")
        if frames and wall:
            self.registry.histogram("vft_video_processed_fps",
                                    buckets=FPS_BUCKETS).observe(
                                        frames / wall)
        with self._state_lock:
            self._last_video = record.get("video")
            self._status_counts[status] = \
                self._status_counts.get(status, 0) + 1

    # -- output health (telemetry/health.py) ---------------------------------
    def health_observe(self, rec: dict) -> None:
        """Fold one feature digest into the per-family manifest roll-up."""
        fam = str(rec.get("feature_type") or "?")
        nonfinite = int(rec.get("nan", 0)) + int(rec.get("inf", 0))
        with self._state_lock:
            h = self._health.setdefault(
                fam, {"records": 0, "nonfinite_records": 0,
                      "nan": 0, "inf": 0})
            h["records"] += 1
            h["nan"] += int(rec.get("nan", 0))
            h["inf"] += int(rec.get("inf", 0))
            if nonfinite:
                h["nonfinite_records"] += 1

    def health_summary(self) -> Dict[str, Dict[str, int]]:
        with self._state_lock:
            return {f: dict(v) for f, v in self._health.items()}

    # -- stage hook (installed on the global profiler) -----------------------
    def _observe_stage(self, name: str, dt: float) -> None:
        self.registry.histogram("vft_stage_seconds", buckets=LATENCY_BUCKETS,
                                stage=name).observe(dt)
        self._run_stages.add(name, dt)
        self._delta_stages.add(name, dt)
        span = current_span()
        if span is not None:
            span.observe_stage(name, dt)

    # -- heartbeats ----------------------------------------------------------
    def _tick(self) -> None:
        self.write_heartbeat()

    def build_heartbeat(self, final: bool = False) -> dict:
        uptime = time.perf_counter() - self._t0
        with self._state_lock:
            status_counts = dict(self._status_counts)
            last_video = self._last_video
        done = sum(status_counts.values())
        vps = round(status_counts.get("done", 0) / uptime, 4) if uptime \
            else 0.0
        self.registry.gauge("vft_videos_per_second").set(vps)
        self.registry.gauge("vft_uptime_seconds").set(round(uptime, 3))
        # drain(): atomic snapshot+reset — the per-interval stage delta a
        # scraper can turn into rates without double counting
        delta = {k: {"s": round(v[0], 6), "calls": v[1]}
                 for k, v in self._delta_stages.drain().items()}
        hb = {
            "schema": "vft.heartbeat/1",
            "run_id": self.run_id,
            "host": socket.gethostname(),
            "host_id": self.host_id,
            "pid": os.getpid(),
            "feature_type": self.feature_type,
            "time": round(time.time(), 3),
            "started_time": round(self._start_time, 3),
            "uptime_s": round(uptime, 3),
            "interval_s": self.interval_s,
            "final": bool(final),
            "videos": status_counts,
            "videos_done": done,
            "videos_per_s": vps,
            "last_video": last_video,
            # heartbeat self-health (telemetry/heartbeat.py): a host whose
            # ticks were failing looks dead to the fleet; the next
            # successful write carries the evidence, so "alive but the
            # liveness channel broke" is distinguishable from "dead"
            "tick_errors": int(self._hb.tick_errors_total),
            "last_tick_error": self._hb.last_tick_error,
            "stage_delta": delta,
            # fan-out backpressure (parallel/fanout.py): per-family queue
            # depth gauges + cumulative blocked/starved totals, so a
            # heartbeat reader can tell WHICH family is the slow consumer
            # (its queue runs full, put_blocked grows) or the starved one
            # (its queue runs empty, get_starved grows) without the trace
            "fanout": self.fanout_snapshot(),
            # feature-cache effectiveness (cache.py): per-family
            # hit/miss/bypass totals + overall hit rate — the first-class
            # bench number ISSUE 7 makes of repeat-content avoidance
            "cache": self.cache_snapshot(),
            # compile-cache effectiveness (compile_cache.py): XLA
            # hit/miss deltas this run + the attached entry's identity
            # and warmth — how vft-fleet proves a joining host skipped
            # its compiles (ISSUE 11)
            "compile_cache": self.compile_cache_snapshot(),
            # roofline accounting (telemetry/roofline.py): per-family
            # effective TFLOPS / MFU / verdict, live — {} when
            # roofline=false, so the off-path heartbeat stays constant
            "roofline": self.roofline_snapshot(),
            # parity observatory (telemetry/parity.py): per-seam digest
            # tallies, live — {} when parity=false, so the off-path
            # heartbeat stays constant
            "parity": self.parity_snapshot(),
        }
        for name, fn in list(self.extra_sections.items()):
            try:
                hb[name] = fn()
            except Exception:
                hb[name] = {"error": "section callback failed"}
        return hb

    def cache_snapshot(self) -> dict:
        """Per-family feature-cache counters pulled out of the registry:
        ``{hits, misses, bypasses}`` each ``{family: n}``, plus the
        overall ``hit_rate`` over consulted lookups (hits+misses; the
        filename-skip bypasses avoided work without consulting cache
        content, so they don't dilute the rate)."""
        out: Dict[str, Dict[str, float]] = {
            "hits": {}, "misses": {}, "bypasses": {}}
        key_of = {"vft_cache_hit_total": "hits",
                  "vft_cache_miss_total": "misses",
                  "vft_cache_bypass_total": "bypasses"}
        for s in self.registry.to_dict()["series"]:
            key = key_of.get(s["name"])
            fam = s.get("labels", {}).get("family")
            if key is None or fam is None:
                continue
            out[key][fam] = int(s.get("value", 0))
        hits = sum(out["hits"].values())
        consulted = hits + sum(out["misses"].values())
        out["hit_rate"] = round(hits / consulted, 4) if consulted else None
        return out

    def compile_cache_snapshot(self) -> dict:
        """XLA compile-cache counters since run start (the jax.monitoring
        listeners' delta) plus — when this process attached a
        fleet-shared entry (compile_cache.py) — its key, warmth at
        attach, and the verify verdicts. ``hits > 0, misses == 0`` is
        the warm-start acceptance shape."""
        s = compile_cache_summary(self._mon_baseline)
        out: Dict[str, object] = {"hits": int(s.get("hits", 0)),
                                  "misses": int(s.get("misses", 0))}
        try:
            from ..compile_cache import active_info
            info = active_info()
        except Exception:
            info = None
        if info is not None:
            out.update(entry=info["entry"], family=info["family"],
                       warm_at_attach=info["warm_at_attach"],
                       verified=info["verified"], dropped=info["dropped"])
        return out

    def roofline_snapshot(self) -> dict:
        """The active roofline observer's light per-family summary
        (telemetry/roofline.py snapshot), ``{}`` when roofline=false —
        like the compile-cache section, the recorder reads the process-
        global subsystem rather than owning it."""
        try:
            from . import roofline
            return roofline.snapshot()
        except Exception:
            return {}

    def parity_snapshot(self) -> dict:
        """The active parity observer's per-seam record tallies
        (telemetry/parity.py snapshot), ``{}`` when parity=false — the
        recorder reads the process-global subsystem rather than owning
        it, exactly like roofline."""
        try:
            from . import parity
            return parity.snapshot()
        except Exception:
            return {}

    def fanout_snapshot(self) -> dict:
        """Per-family fan-out backpressure series pulled out of the
        registry: ``{queue_depth, put_blocked_ms_total,
        get_starved_ms_total}``, each ``{family: value}`` (empty dicts
        outside multi-family runs)."""
        out: Dict[str, Dict[str, float]] = {
            "queue_depth": {}, "put_blocked_ms_total": {},
            "get_starved_ms_total": {}}
        key_of = {"vft_fanout_queue_depth": "queue_depth",
                  "vft_fanout_put_blocked_ms_total": "put_blocked_ms_total",
                  "vft_fanout_get_starved_ms_total": "get_starved_ms_total"}
        for s in self.registry.to_dict()["series"]:
            key = key_of.get(s["name"])
            fam = s.get("labels", {}).get("family")
            if key is None or fam is None:
                continue
            out[key][fam] = round(float(s.get("value", 0.0)), 3)
        return out

    def write_heartbeat(self, final: bool = False) -> None:
        hb = self.build_heartbeat(final=final)
        jsonl.write_json_atomic(self.heartbeat_path, hb)
        for fn in list(self.tick_hooks):
            try:
                fn(hb)
            except Exception as e:
                # hooks observe; they must never break liveness — but a
                # silently-dead retention/alerting channel is its own
                # incident, so the first failure is named
                self._tick_hook_errors += 1
                if self._tick_hook_errors == 1:
                    print(f"telemetry: heartbeat hook failed: "
                          f"{type(e).__name__}: {e}")

    # -- manifest ------------------------------------------------------------
    def build_manifest(self, *, tally: Optional[Dict[str, int]] = None,
                       wall_s: Optional[float] = None,
                       failure_tallies: Optional[Dict[str, int]] = None,
                       roofline: Optional[dict] = None) -> dict:
        with self._state_lock:
            tally = dict(tally if tally is not None else self._status_counts)
        stage_totals = {k: {"s": round(v[0], 6), "calls": v[1]}
                        for k, v in self._run_stages.snapshot().items()}
        return manifest.build_manifest(
            run_config=self.run_config,
            feature_type=self.feature_type,
            host_id=self.host_id,
            run_id=self.run_id,
            health=self.health_summary(),
            started_time=round(self._start_time, 3),
            wall_s=wall_s if wall_s is not None
            else time.perf_counter() - self._t0,
            tally=tally,
            failure_tallies=failure_tallies,
            stage_totals=stage_totals,
            metrics_dump=self.registry.to_dict(),
            # raw event deltas PLUS the attached fleet-entry identity
            # (compile_cache.py), so the manifest alone answers "did
            # this host join warm" (hits/misses keys win over raw names)
            compile_cache={**compile_cache_summary(self._mon_baseline),
                           **{k: v for k, v in
                              (self.compile_cache_snapshot()).items()
                              if k not in ("hits", "misses")}},
            roofline=roofline,
        )
