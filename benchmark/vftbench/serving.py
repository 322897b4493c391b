"""The served path: a ``ServeLoop`` in this process, a spool client that
offers it load, and the two drivers that differ only in when a request is due.

``backlog`` is a closed loop: a fixed number of requests is always
outstanding, the operator's full spool. ``poisson`` is an open loop: requests
are due on a seeded schedule whatever the server does, the caller's traffic.
Both run the same client loop on the main thread, which polls the spool for
responses every two milliseconds and does nothing else inside the window:
responses are read, artifacts checked and files deleted after it.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from . import arrivals, corpus, device, program
from .manifest import Cell
from .measurement import Measurement
from .tracing import TraceWindow

POLL_S = 0.002


class Session:
    """One ``ServeLoop`` over a fresh spool under ``run_dir``."""

    def __init__(self, cell: Cell, run_dir: Path, suffix: str) -> None:
        from video_features_tpu import serve
        self.serve = serve
        self.suffix = suffix  # of a request's file: the corpus kind's
        self.run_dir = Path(run_dir)
        self.links = self.run_dir / "links"
        self.links.mkdir(parents=True)
        self.args = program.program_args(cell.config, self.run_dir)
        self.spool = str(self.args.spool_dir)
        self.loop = serve.ServeLoop(self.args,
                                    out_root=str(self.args.output_path))
        self.runner = self.loop.extractor.runner
        self.keys = list(self.loop.extractor.output_feat_keys)
        self.feature_key = str(self.args.feature_type)
        self._done_dir = os.path.join(self.spool, serve.DONE_DIR)
        self._thread = threading.Thread(target=self.loop.run,
                                        name="vftbench-serve", daemon=True)
        self._thread.start()

    def _link(self, rid: str) -> str:
        return str(self.links / f"{rid}{self.suffix}")

    def submit(self, rid: str, video_path: str) -> None:
        """One request for one video under a stem of its own: the sink skips
        a stem it has written before."""
        os.symlink(video_path, self._link(rid))
        self.serve.submit_request(self.spool, [self._link(rid)],
                                  request_id=rid)

    def answered(self, rid: str) -> bool:
        return os.path.exists(os.path.join(self._done_dir, f"{rid}.json"))

    def response(self, rid: str) -> Optional[dict]:
        return self.serve.read_response(self.spool, rid)

    def wait(self, rid: str, timeout_s: float) -> Optional[dict]:
        deadline = time.perf_counter() + timeout_s
        while not self.answered(rid):
            if time.perf_counter() > deadline:
                raise TimeoutError(f"request {rid}: no response within "
                                   f"{timeout_s} s")
            if not self._thread.is_alive():
                raise RuntimeError("the serve loop ended during set-up")
            time.sleep(POLL_S)
        return self.response(rid)

    def features(self, rid: str) -> Dict[str, np.ndarray]:
        return {k: np.load(program.artifact_path(self.args, self._link(rid),
                                                 k)) for k in self.keys}

    def discard(self, rid: str) -> None:
        paths = [program.artifact_path(self.args, self._link(rid), k)
                 for k in self.keys] + [self._link(rid)]
        for p in paths:
            try:
                os.unlink(p)
            except OSError:
                pass

    def close(self) -> None:
        self.loop.stop()
        self._thread.join(timeout=60.0)
        if self._thread.is_alive():
            raise RuntimeError("the serve loop did not stop within 60 s")


def response_ok(resp: Optional[dict]) -> bool:
    if not resp or resp.get("status") != "done":
        return False
    statuses = [s for per in (resp.get("videos") or {}).values()
                for s in per.values()]
    return bool(statuses) and all(s == "done" for s in statuses)


def warm_up(session: Session, check_video: str) -> Dict[str, np.ndarray]:
    """One real request, whose dispatches show the unit's wire shape, then a
    batch of zeros through every wire batch size. Returns the real request's
    features: the served side of the check against float32."""
    seen: List[tuple] = []
    unwatch = program.watch_dispatch(session.runner, seen)
    try:
        session.submit("warm-check", check_video)
        resp = session.wait("warm-check", timeout_s=900.0)
    finally:
        unwatch()
    if not response_ok(resp):
        raise RuntimeError(f"the warm-up request failed: {resp}")
    feats = session.features("warm-check")
    session.discard("warm-check")
    unit_shape, dtype = program.unit_on_the_wire(seen)
    ladder = program.warm_ladder(session.runner, unit_shape, dtype)
    print(f"vftbench: warmed wire batches {ladder} of unit {unit_shape} "
          f"{dtype}; serve workers {session.loop.workers}; "
          f"cpu_count {os.cpu_count()}")
    return feats


def trace_at(window: TraceWindow, at: float, length: float) -> None:
    """Trace ``length`` seconds from ``at`` on; runs on a thread of its own
    so that the client loop keeps polling while the profiler starts and
    writes."""
    time.sleep(max(0.0, at - time.perf_counter()))
    window.start()
    time.sleep(length)
    window.stop()


def offer_load(session: Session, m: Measurement, videos: List[dict],
               order: List[int], due: Optional[List[float]], target: int,
               ramp_s: float, seconds: float,
               on_open: Callable[[], None]) -> Dict[str, Any]:
    """The client loop over ramp, window and drain. ``due`` (seconds from
    the loop's start) makes it an open loop; ``None`` keeps ``target``
    requests outstanding. The window opens ``ramp_s`` into the loop. Sets
    ``m.t0``/``m.t1``, calls ``on_open`` as the window opens, and returns the
    requests and the compiles counted in the window."""
    requests: List[Dict[str, Any]] = []
    outstanding: Dict[str, Dict[str, Any]] = {}
    edges: Dict[str, Any] = {}
    begin = time.perf_counter()
    m.t0 = m.t1 = float("inf")

    def next_due(now: float) -> Optional[float]:
        if due is None:
            return now if len(outstanding) < target else None
        i = len(requests)
        return begin + due[i] if i < len(due) and begin + due[i] <= now \
            else None

    while True:
        now = time.perf_counter()
        for rid in [r for r in outstanding if session.answered(r)]:
            outstanding.pop(rid)["visible"] = now
        if "open" not in edges and now >= begin + ramp_s:
            m.t0, m.t1 = now, now + seconds
            edges["open"] = (program.compile_events(), os.times())
            on_open()
        if "close" not in edges and now >= m.t1:
            edges["close"] = (program.compile_events(), os.times())
        if now < m.t1:
            when = next_due(now)
            while when is not None:
                video = videos[order[len(requests) % len(videos)]]
                req = {"rid": f"q{len(requests):06d}", "due": when,
                       "units": video["units"],
                       "taken_up": time.perf_counter()}
                session.submit(req["rid"], video["path"])
                requests.append(req)
                outstanding[req["rid"]] = req
                when = next_due(time.perf_counter())
        elif not outstanding or now >= m.t1 + m.drain_limit_s:
            break
        time.sleep(POLL_S)
    (c0, cpu0), (c1, cpu1) = edges["open"], edges["close"]
    m.cpu_s = (cpu1.user + cpu1.system) - (cpu0.user + cpu0.system)
    return {"requests": requests, "compiles_in_window": c1 - c0}


def check_and_discard(session: Session, m: Measurement,
                      requests: List[Dict[str, Any]],
                      validate: Callable) -> List[str]:
    """After the window: every request needs a ``done`` response and sound
    artifacts. Fills ``m.completions`` and ``m.responses``; returns what
    failed, one line each."""
    failed = []
    for req in requests:
        resp = session.response(req["rid"]) if "visible" in req else None
        if not response_ok(resp):
            problem = f"response {json.dumps(resp)[:300]}"
        else:
            problem = validate(session.features(req["rid"]),
                               session.feature_key, req["units"])
        if problem:
            failed.append(f"{req['rid']}: {problem}")
            req.pop("visible", None)  # counts as unanswered from here on
        else:
            m.completions.append((req["visible"], req["units"]))
            if m.t0 <= req["visible"] < m.t1:
                m.responses.append(resp)
        session.discard(req["rid"])
    return failed


def run(cell: Cell, seed: int, seconds: float, trace: bool, out_dir: Path,
        started: float) -> Dict[str, Any]:
    """Set up, warm up, measure and check one served cell."""
    traffic, unit = cell.traffic, cell.config["unit"]
    run_dir = out_dir / "run"
    shutil.rmtree(run_dir, ignore_errors=True)
    run_dir.mkdir(parents=True)
    spec = cell.corpus_spec()
    kind = cell.corpus_kind(spec)

    def build_corpus():
        return (corpus.build(out_dir.parent, spec, seed, kind),
                corpus.build_fixed(out_dir.parent, spec, [corpus.frames_for(
                    int(cell.config["check_units"]), unit)], kind))

    # the corpus is encoded on threads of its own while the extractor is
    # built: neither waits for the other
    side = ThreadPoolExecutor(max_workers=1, thread_name_prefix="vftbench")
    making = side.submit(build_corpus)
    session = Session(cell, run_dir, kind.SUFFIX)
    built, check = making.result()
    videos = [{**v, "units": corpus.units_of(v["frames"], unit)}
              for v in built]
    (check_video,) = check.values()

    m = Measurement()
    m.drain_limit_s = float(traffic["drain_s"])
    m.latency_limit_s = traffic.get("latency_limit_s")
    unhook: List[Callable[[], None]] = []
    dispatches: List[tuple] = []
    try:
        check_feats = warm_up(session, check_video)
        ramp_s = float(traffic["ramp_s"])
        order = [int(i) for i in corpus.stream(
            seed, cell.traffic_name, "order").permutation(len(videos))]
        due, target = None, 0
        if traffic["driver"] == "poisson":
            due = arrivals.schedule(traffic["arrivals"], ramp_s + seconds,
                                    seed, cell.traffic_name)
        else:
            target = int(round(float(traffic["outstanding_per_worker"])
                               * session.loop.workers))
        if trace:
            # the program's span tree in memory, started here and not by
            # the stage listener's subscription below
            unhook.append(program.start_recorder())
            unhook.append(program.collect_stage_spans(m.stage_spans))
            unhook.append(program.watch_dispatch(session.runner, dispatches))
        window = TraceWindow(out_dir / "trace", m) if trace else None
        traced: List[Any] = []  # the future of the traced sub-window

        def window_opens() -> None:
            m.setup_s = m.t0 - started
            m.memory_peak_at_open_bytes = device.memory_peak_bytes(
                device.chips_of(cell.chips))
            if window is not None:
                shutil.rmtree(window.out_dir, ignore_errors=True)
                length = min(float(traffic["trace_s"]), 0.6 * seconds)
                traced.append(side.submit(
                    trace_at, window, m.t0 + 0.3 * (seconds - length),
                    length))

        offered = offer_load(session, m, videos, order, due, target, ramp_s,
                             seconds, window_opens)
        for undo in reversed(unhook):
            undo()
        if traced:
            traced[0].result()  # raises what the tracing thread raised
            print(f"vftbench: {window.took()}, on a thread of its own")
    except BaseException:
        session.loop.stop()
        raise
    finally:
        side.shutdown(wait=True)
    requests = offered["requests"]
    m.requests = requests
    m.dispatches = [(at, shape[0], padded)
                    for at, shape, _, padded in dispatches]
    failed = check_and_discard(session, m, requests,
                               cell.config_function("checks", "validate"))
    session.close()
    return {"measurement": m, "attempted": len(requests), "failed": failed,
            "compiles_in_window": offered["compiles_in_window"],
            "lateness_s": ([r["taken_up"] - r["due"] for r in requests]
                           if due is not None else []),
            "check_video": check_video, "check_feats": check_feats,
            "extractor": session.loop.extractor}
