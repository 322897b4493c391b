"""Shared-decode fan-out: ONE decode pass per video feeding N families.

The reference toolkit (and this CLI until now) runs one model family per
invocation, so extracting the common resnet+clip+s3d+vggish bundle for a
corpus pays the full video decode cost once PER FAMILY — and on real
hosts decode is the wall (docs/performance.md: ~3.2 ms/frame of cv2
decode vs ~0.36 ms of transform; the sustained r21d pipeline is
decode-bound at 19.2 clips/s while the chip sustains ~1,515). This
module amortizes one decode pass across every requested consumer:

  :class:`FrameBus`
      One video's single decoder (utils/io.py ``_FrameStream``, the same
      missing-frame-0 workaround and grab()-skip economy as the serial
      path) walking the UNION of all subscribers' frame-selection plans.
      Each subscriber's plan is computed with the very
      ``plan_frame_selection``/``fps_filter_map`` walk ``VideoSource``
      uses, so a source frame needed by any family is decoded exactly
      once and every family's delivered (frame, timestamp, index) stream
      is bit-identical to what its own private ``VideoSource`` would
      have produced (pinned by tests/test_multi_family.py). Frames decode
      in native BGR; the RGB reorder happens at most once per frame no
      matter how many subscribers want RGB.

  :class:`SharedFrameSource`
      A subscriber's end of the bus, with the ``VideoSource`` observable
      surface (``fps``/``num_frames``/``frames()``/batched ``__iter__``/
      thread-safe ``cancel()``), drawing raw frames from a bounded queue
      (backpressure: the decoder blocks when a family falls behind,
      bounding host memory at ``depth`` frames per family) and applying
      the family's own host transform on the family's thread — so N
      transforms and N families' device programs are all in flight
      concurrently over one decode. A closed/cancelled subscriber is
      skipped by the bus, never wedging the other families (per-family
      fault isolation).

  :class:`SharedDecodeSession`
      The per-(video, run) umbrella the MultiExtractor installs
      thread-locally on each family's thread (:func:`use_session`):
      visual families reach the bus through
      ``BaseExtractor.video_source``; audio families share one wav rip
      (vggish) instead of re-running ffmpeg per family.

Subscription protocol: the bus is constructed with the set of expected
families; each family either ``subscribe()``\\ s (blocking until every
expected family has arrived, then returning a fully-probed source) or is
marked ``done()`` (skipped / failed before subscribing), and decode
starts once all have arrived. A family retrying after a mid-stream
failure gets ``None`` from ``subscribe`` (the one-shot pass has already
flowed) and falls back to a private ``VideoSource`` — isolation over
sharing for the rare retry.
"""
from __future__ import annotations

import os
import threading
import time
from contextlib import contextmanager
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from .. import telemetry
from ..telemetry import trace
from ..utils.faults import DeadlineExceeded
from ..utils.io import (CHANNEL_ORDERS, _batched, _FrameStream,
                        convert_decoded, count_frames_by_decode,
                        get_video_props, plan_frame_selection)

#: default per-subscriber queue depth (raw decoded frames; a 320x240
#: frame is ~230 KB, so the default bounds each family at ~15 MB)
DEFAULT_DEPTH = 64

_tls = threading.local()


def current_session() -> Optional["SharedDecodeSession"]:
    """The shared-decode session installed on THIS thread, if any."""
    return getattr(_tls, "session", None)


@contextmanager
def use_session(session: Optional["SharedDecodeSession"]) -> Iterator[None]:
    """Install ``session`` thread-locally for a block — how the
    MultiExtractor's per-family threads route ``video_source``/wav-rip
    calls to the shared pass without changing extractor signatures."""
    prev = getattr(_tls, "session", None)
    _tls.session = session
    try:
        yield
    finally:
        _tls.session = prev


class SharedFrameSource:
    """One family's subscription: the consumer half mimics ``VideoSource``.

    Constructed by :meth:`FrameBus.subscribe`; plan fields (``fps``,
    ``index_map``, ``num_frames``, source props) are filled in by the bus
    before ``subscribe`` returns, so extractors can read them exactly as
    they would off a private source.
    """

    def __init__(self, bus: "FrameBus", family: str, *, batch_size: int = 1,
                 fps: Optional[float] = None, total: Optional[int] = None,
                 transform: Optional[Callable] = None, overlap: int = 0,
                 channel_order: str = "rgb", depth: int = DEFAULT_DEPTH):
        import queue as _queue
        assert isinstance(batch_size, int) and batch_size > 0
        assert isinstance(overlap, int) and 0 <= overlap < batch_size
        assert channel_order in CHANNEL_ORDERS, channel_order
        if fps is not None and total is not None:
            raise ValueError("'fps' and 'total' are mutually exclusive")
        self.bus = bus
        self.family = str(family)
        self.path = bus.path
        self.batch_size = batch_size
        self.overlap = overlap
        self.transform = transform
        self.channel_order = channel_order
        self._want_fps = None if fps is None else float(fps)
        self._want_total = None if total is None else int(total)
        self.queue: "_queue.Queue" = _queue.Queue(maxsize=max(int(depth), 2))
        self.closed = False
        self._cancelled = False
        self._cancel_reason = ""
        self._error: Optional[str] = None
        #: ms of shared decode wall time that had run when this family's
        #: stream completed — the telemetry attribution field
        #: (``decode_shared_ms`` on the family's video span)
        self.decode_shared_ms: Optional[float] = None
        #: cumulative backpressure seconds, both directions: the decoder
        #: blocked on THIS family's full queue (put_blocked — this family
        #: is the slow consumer holding everyone back) vs this family
        #: blocked on an empty queue (get_starved — decode is the wall).
        #: Mirrored into vft_fanout_*_ms_total{family=} counters and the
        #: heartbeat "fanout" section; stalls past trace.STALL_MIN_S also
        #: become timeline events.
        self.put_blocked_s = 0.0
        self.get_starved_s = 0.0
        # plan fields, set by the bus at finalize time
        self.fps: float = 0.0
        self.index_map: Optional[np.ndarray] = None
        self.num_frames: int = 0
        self.src_fps: float = 0.0
        self.src_num_frames: int = 0
        self.height = self.width = 0

    # -- bus side -----------------------------------------------------------
    def _set_plan(self, out_fps: float, index_map: Optional[np.ndarray],
                  num_frames: int, src_fps: float, src_num_frames: int,
                  height: int, width: int) -> None:
        self.fps = out_fps
        self.index_map = index_map
        self.num_frames = num_frames
        self.src_fps = src_fps
        self.src_num_frames = src_num_frames
        self.height, self.width = height, width

    def _push(self, item) -> bool:
        """Bounded put that gives up when this subscriber is gone — one
        family abandoning its stream must never wedge the bus (and
        thereby every other family). A put that found the queue full is
        backpressure — the decoder outran this family — and is accounted
        as put-blocked time (counter + trace span + depth gauge)."""
        import queue as _queue
        try:
            # uncontended fast path: a non-full queue costs no timing call
            self.queue.put_nowait(item)
            telemetry.gauge_set("vft_fanout_queue_depth",
                                self.queue.qsize(), family=self.family)
            return True
        except _queue.Full:
            pass
        t0 = time.perf_counter()
        ok = False
        while not self.closed:
            try:
                self.queue.put(item, timeout=0.1)
                ok = True
                break
            except _queue.Full:
                continue
        self._account_put_blocked(t0)
        if ok:
            telemetry.gauge_set("vft_fanout_queue_depth",
                                self.queue.qsize(), family=self.family)
        return ok

    def _account_put_blocked(self, t0: float) -> None:
        dt = time.perf_counter() - t0
        self.put_blocked_s += dt
        telemetry.inc("vft_fanout_put_blocked_ms_total", dt * 1e3,
                      family=self.family)
        tr = trace.active()
        if tr is not None and dt >= trace.STALL_MIN_S:
            tr.complete("fanout.put_blocked", t0, dt, family=self.family)
            tr.counter(f"fanout_queue_depth/{self.family}",
                       self.queue.qsize())

    # -- consumer side ------------------------------------------------------
    def __len__(self) -> int:
        return self.num_frames

    def _raise_if_cancelled(self) -> None:
        if self._cancelled:
            raise DeadlineExceeded(f"{self.path}: {self._cancel_reason}")

    def frames(self) -> Iterator[Tuple[np.ndarray, float, int]]:
        """(frame, timestamp_ms, out_index) with the family's transform
        applied on THIS thread — same contract as VideoSource.frames()."""
        import queue as _queue

        from ..utils.profiling import profiler
        tf = self.transform
        try:
            while True:
                self._raise_if_cancelled()
                t_wait = time.perf_counter()
                while True:
                    try:
                        # 1s poll (not one long get) bounds how stale the
                        # cancellation/liveness checks can be
                        tag, payload = self.queue.get(timeout=1.0)
                        break
                    except _queue.Empty:
                        self._raise_if_cancelled()
                        t = self.bus._thread
                        if t is not None and t.is_alive():
                            continue
                        # the bus may have flushed its tail and exited
                        # between the timeout and the liveness check:
                        # drain first
                        try:
                            tag, payload = self.queue.get_nowait()
                            break
                        except _queue.Empty:
                            err = self._error
                            raise RuntimeError(
                                f"shared decode for {self.path} " +
                                (f"failed: {err}" if err
                                 else "died without a result")) from None
                # time spent inside get() is time THIS family sat idle
                # waiting on the shared decoder (starvation)
                waited = time.perf_counter() - t_wait
                self.get_starved_s += waited
                telemetry.inc("vft_fanout_get_starved_ms_total",
                              waited * 1e3, family=self.family)
                tr = trace.active()
                if tr is not None and waited >= trace.STALL_MIN_S:
                    tr.complete("fanout.get_starved", t_wait, waited,
                                family=self.family)
                if tag == "frame":
                    raw, out_idx = payload
                    with profiler.stage("decode"):
                        if tf is None:
                            x = raw
                        else:
                            with trace.span("decode.transform"):
                                x = tf(raw)
                    yield x, out_idx / self.fps * 1000.0, out_idx
                elif tag == "done":
                    return
                else:
                    raise RuntimeError(
                        f"shared decode failed for {self.path}: {payload}")
        finally:
            self.close()

    def __iter__(self):
        return _batched(self.frames(), self.batch_size, self.overlap)

    def cancel(self, reason: str = "cancelled") -> None:
        """Thread-safe kill (deadline watchdog): closes only THIS
        family's subscription; the bus keeps serving the others."""
        self._cancel_reason = reason or "cancelled"
        self._cancelled = True
        self.close()

    def release(self) -> None:
        self.close()

    def close(self) -> None:
        """Mark abandoned and drain, so a bus blocked in a bounded put
        sees ``closed`` within its poll interval."""
        self.closed = True
        try:
            while True:
                self.queue.get_nowait()
        except Exception:
            pass


class FrameBus:
    """One shared decode pass over the union of N families' frame plans."""

    def __init__(self, path, expected_families: Sequence[str],
                 depth: int = DEFAULT_DEPTH):
        self.path = str(path)
        self.expected = frozenset(str(f) for f in expected_families)
        self.depth = int(depth)
        self._cond = threading.Condition()
        self._subs: Dict[str, SharedFrameSource] = {}
        self._done_families: set = set()
        self._finalizing = False
        self._plans_ready = False
        self._started = False
        self._probe_error: Optional[str] = None
        self._thread: Optional[threading.Thread] = None
        self._cancelled = False
        #: cumulative shared decode seconds (read/skip/cvtColor); written
        #: only by the decode thread, read for per-family attribution
        self._decode_s = 0.0

    # -- family-side API ----------------------------------------------------
    def subscribe(self, family: str, *, batch_size: int = 1,
                  fps: Optional[float] = None, total: Optional[int] = None,
                  transform: Optional[Callable] = None, overlap: int = 0,
                  channel_order: str = "rgb",
                  **unsupported) -> Optional[SharedFrameSource]:
        """Join the shared pass; blocks until every expected family has
        arrived and the plans are probed, then returns the source.

        Returns ``None`` (caller falls back to a private VideoSource)
        when: the family is not expected, it already subscribed once
        (retry attempts), decode already started, or the caller needs a
        knob the shared pass cannot honor (e.g. ``fps_mode=reencode`` —
        per-family lossy temp-file provenance cannot share one decode).
        """
        family = str(family)
        if any(v not in (None, "select", False) for v in
               unsupported.values()):
            return None
        with self._cond:
            if (family not in self.expected or family in self._subs
                    or family in self._done_families or self._started):
                return None
            sub = SharedFrameSource(
                self, family, batch_size=batch_size, fps=fps, total=total,
                transform=transform, overlap=overlap,
                channel_order=channel_order, depth=self.depth)
            self._subs[family] = sub
        # register with the calling attempt's fault context BEFORE the
        # barrier wait below: the per-video deadline watchdog must be able
        # to cancel a family blocked waiting for its siblings to arrive
        from ..utils import faults
        ctx = faults.current_context()
        if ctx is not None:
            ctx.register(sub)
        self._maybe_finalize()
        t_wait = time.perf_counter()
        with self._cond:
            while not self._plans_ready and self._probe_error is None \
                    and not sub._cancelled:
                self._cond.wait(0.1)
            waited = time.perf_counter() - t_wait
            tr = trace.active()
            if tr is not None and waited >= trace.STALL_MIN_S:
                # arrival-barrier stall: this family sat waiting for its
                # siblings to subscribe (or the probe to finish) — the
                # first suspect when a multi-family run's lanes start late
                tr.complete("fanout.subscribe_wait", t_wait, waited,
                            family=family)
            if sub._cancelled:
                sub._raise_if_cancelled()
            if self._probe_error is not None:
                # a fresh exception per waiter (sharing one instance across
                # N raising threads races traceback mutation); the embedded
                # type name keeps utils/faults.classify's marker logic
                # working exactly like the decode-worker protocol
                raise RuntimeError(f"shared decode probe failed for "
                                   f"{self.path}: {self._probe_error}")
        return sub

    def done(self, family: str) -> None:
        """Mark ``family`` as never-going-to-subscribe(-again): skipped,
        quarantined, failed before reaching the decoder, or finished.
        Idempotent; the barrier releases once every expected family has
        subscribed or is done."""
        family = str(family)
        with self._cond:
            if family in self._done_families:
                return
            self._done_families.add(family)
        self._maybe_finalize()

    def shared_ms(self, family: str) -> Optional[float]:
        sub = self._subs.get(str(family))
        return None if sub is None else sub.decode_shared_ms

    def cancel(self, reason: str = "cancelled") -> None:
        """Cancel the whole pass (every family fails with
        DeadlineExceeded semantics via its own source cancel). The bus
        thread sees the flag before its next source frame and releases
        the stream it opened; no other thread touches that capture."""
        self._cancelled = True
        with self._cond:
            subs = list(self._subs.values())
            self._cond.notify_all()
        for s in subs:
            s.cancel(reason)

    # -- barrier + plan probing ---------------------------------------------
    def _all_arrived(self) -> bool:
        return self.expected <= (set(self._subs) | self._done_families)

    def _maybe_finalize(self) -> None:
        with self._cond:
            if self._finalizing or not self._all_arrived():
                return
            self._finalizing = True
            subs = list(self._subs.values())
        try:
            if subs:
                props = get_video_props(self.path)
                src_fps, n = props["fps"], props["num_frames"]
                if n <= 0:
                    # metadata lied; every plan (and truncation warning)
                    # needs a real count — same recount the serial
                    # resampling path performs
                    n = count_frames_by_decode(self.path)
                    if n == 0:
                        raise ValueError(
                            f"No decodable frames in {self.path}")
                for s in subs:
                    out_fps, index_map, num = plan_frame_selection(
                        src_fps, n, fps=s._want_fps, total=s._want_total)
                    s._set_plan(out_fps, index_map, num, src_fps, n,
                                props["height"], props["width"])
        except BaseException as e:
            with self._cond:
                self._probe_error = f"{type(e).__name__}: {e}"
                self._started = True  # no decode will run
                self._cond.notify_all()
            return
        with self._cond:
            self._plans_ready = True
            self._started = True
            self._cond.notify_all()
        if subs:
            self._thread = threading.Thread(
                target=self._decode, name="vft-fanout-decode", daemon=True)
            self._thread.start()

    # -- the single decode pass ---------------------------------------------
    def _finish_sub(self, sub: SharedFrameSource, emitted: int) -> None:
        sub.decode_shared_ms = round(self._decode_s * 1000.0, 3)
        sub._push(("done", emitted))

    def _decode(self) -> None:
        from ..utils.profiling import profiler
        subs = list(self._subs.values())
        ptrs = {s.family: 0 for s in subs}
        emitted = {s.family: 0 for s in subs}
        finished: set = set()
        t_pass = time.perf_counter()
        stream = _FrameStream(self.path, channel_order="bgr")
        try:
            src_idx = 0
            while not self._cancelled:
                # union step: which open subscribers need THIS src frame,
                # and does anyone still need a future one?
                wants: List[Tuple[SharedFrameSource, List[int]]] = []
                pending = False
                for s in subs:
                    if s.family in finished or s.closed:
                        continue
                    if s.index_map is None:
                        # native delivery: every frame until EOF
                        wants.append((s, [src_idx]))
                        pending = True
                        continue
                    m = s.index_map
                    p = ptrs[s.family]
                    outs: List[int] = []
                    while p < len(m) and int(m[p]) == src_idx:
                        outs.append(p)  # duplication on upsampling
                        p += 1
                    ptrs[s.family] = p
                    if outs:
                        wants.append((s, outs))
                    if p < len(m):
                        pending = True
                if not wants and not pending:
                    break  # every plan satisfied
                t0 = time.perf_counter()
                with profiler.stage("decode"), trace.span(
                        "decode.read" if wants else "decode.skip"):
                    if wants:
                        frame = stream.read()
                        ok = frame is not None
                    else:
                        # nobody materializes this frame: grab()-skip it
                        # (decode only, no YUV->BGR conversion/copy)
                        ok = stream.skip()
                        frame = None
                self._decode_s += time.perf_counter() - t0
                if not ok:
                    break  # EOF (possibly before the plans: see below)
                if frame is not None:
                    # each delivery format ('rgb' reorder / 'i420' pack) is
                    # converted AT MOST ONCE per source frame no matter how
                    # many subscribers want it; 'bgr' shares the decoder's
                    # native buffer with zero conversion
                    by_order = {"bgr": frame}
                    for s, outs in wants:
                        if s.closed:
                            continue
                        arr = by_order.get(s.channel_order)
                        if arr is None:
                            t1 = time.perf_counter()
                            with profiler.stage("decode"), \
                                    trace.span("decode.ingest"):
                                arr = convert_decoded(frame, s.channel_order)
                            self._decode_s += time.perf_counter() - t1
                            by_order[s.channel_order] = arr
                        for out_idx in outs:
                            if not s._push(("frame", (arr, out_idx))):
                                break  # subscriber abandoned mid-frame
                            emitted[s.family] += 1
                    for s in subs:
                        if s.family in finished or s.closed \
                                or s.index_map is None:
                            continue
                        if ptrs[s.family] >= len(s.index_map):
                            finished.add(s.family)
                            self._finish_sub(s, emitted[s.family])
                src_idx += 1
            for s in subs:
                if s.family in finished:
                    continue
                if self._cancelled:
                    s.cancel("shared decode cancelled")
                    continue
                if s.index_map is not None \
                        and emitted[s.family] < len(s.index_map) \
                        and not s.closed:
                    # container metadata overstated the frame count; same
                    # truncation warning contract as the serial path
                    print(f"Warning: {self.path} ended after {src_idx} "
                          f"frames (metadata said {s.src_num_frames}); "
                          f"{s.family} emitted {emitted[s.family]}/"
                          f"{len(s.index_map)} resampled frames.")
                self._finish_sub(s, emitted[s.family])
        except BaseException as e:
            # the forwarded string keeps the exception's name AND message
            # (str(OSError) includes the strerror), so the subscribers'
            # classify() sees the same POISON/FATAL markers an inline
            # failure would — an injected ENOSPC inside the bus must not
            # soften into a retried TRANSIENT on the family side
            # (utils/faults.py _FATAL_MARKERS; utils/inject.py)
            msg = f"{type(e).__name__}: {e}"
            telemetry.inc("vft_fanout_decode_errors_total")
            for s in subs:
                if s.family in finished:
                    continue
                s._error = msg
                s._push(("error", msg))
        finally:
            stream.release()
            # one umbrella span over the whole union pass: on the bus
            # thread's lane it brackets the per-frame decode stage spans,
            # and its gaps ARE the put-blocked stalls
            trace.complete("fanout.decode_pass", t_pass,
                           time.perf_counter() - t_pass, video=self.path,
                           families=len(subs))


class SharedDecodeSession:
    """Per-(video, run) shared resources: the visual-family FrameBus and
    the one-rip-per-video wav cache for audio families."""

    def __init__(self, video_path, visual_families: Sequence[str],
                 depth: int = DEFAULT_DEPTH):
        self.video_path = str(video_path)
        self.bus: Optional[FrameBus] = (
            FrameBus(video_path, visual_families, depth=depth)
            if visual_families else None)
        self._wav_lock = threading.Lock()
        self._wav: Optional[Tuple[str, str]] = None
        self._wav_error: Optional[str] = None

    # -- visual -------------------------------------------------------------
    def subscribe(self, family: str, **kwargs
                  ) -> Optional[SharedFrameSource]:
        if self.bus is None:
            return None
        return self.bus.subscribe(family, **kwargs)

    def family_done(self, family: str) -> None:
        if self.bus is not None:
            self.bus.done(family)

    def shared_ms(self, family: str) -> Optional[float]:
        if self.bus is None:
            return None
        return self.bus.shared_ms(family)

    # -- audio --------------------------------------------------------------
    def shared_wav(self, video_path, tmp_path, ripper: Callable) -> str:
        """Rip the audio track once; every audio family reads the same
        wav. The SESSION owns cleanup (``cleanup()``), so a family must
        not delete what its siblings may still be reading."""
        with self._wav_lock:
            if self._wav_error is not None:
                # embed the original type name so classify()'s marker
                # logic treats the replay like the first failure
                raise RuntimeError(f"shared wav rip failed for "
                                   f"{video_path}: {self._wav_error}")
            if self._wav is None:
                try:
                    with trace.span("wav_rip", video=str(video_path),
                                    shared=True):
                        self._wav = ripper(video_path, tmp_path)
                except BaseException as e:
                    self._wav_error = f"{type(e).__name__}: {e}"
                    raise
            return self._wav[0]

    def cleanup(self, keep_tmp: bool = False) -> None:
        """Drop the shared wav/aac temps (unless ``keep_tmp``); called by
        the MultiExtractor after every family's thread has joined."""
        with self._wav_lock:
            wav, self._wav = self._wav, None
        if wav and not keep_tmp:
            for p in wav:
                try:
                    os.remove(p)
                except OSError:
                    pass
