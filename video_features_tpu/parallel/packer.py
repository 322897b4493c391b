"""Cross-video clip batching: fill fixed-shape device groups from several
videos' clips at once.

Per-video async streams (parallel/mesh.py FeatureStream) dispatch each
video's trailing group ragged — padded rows that burn device FLOPs. At the
bench sweet spot (``clip_batch_size=128`` on v5e) the 18 s reference sample
yields 22 clips, so 83% of a per-video flagship group would be padding and
the measured steady state is unreachable on short-video corpora. The
packer instead keeps ONE buffer shared by the ``video_workers`` decode
threads: a device group dispatches only when FULL (the sole exception is
the final drain, when every still-open video is already waiting to close),
so sustained throughput approaches the fixed-shape bench steady state
regardless of per-video clip counts.

Ordering contract: results come back per video, in that video's clip
order, bit-identical to the unpacked path — group membership only changes
which padded rows surround a clip, and the row itself is independent of
its neighbors (the forward is row-wise; parity asserted in
tests/test_packer.py).

Reference contrast: the reference's only cross-video parallelism is
launching extra whole processes per GPU (reference README.md:70-84), each
still running batch=1 slices; it has no batch packing of any kind.

Concurrency design (all state under one lock; D2H copies outside it):

  - ``add`` appends to the shared buffer; a full buffer dispatches the
    jitted forward immediately (dispatch is async — enqueue only).
  - ``close_video`` blocks until all of that video's clips have
    materialized. Progress is guaranteed: whoever observes work in flight
    drains the oldest group (a second lock keeps drains submit-ordered);
    when every open video is simultaneously closing and clips still sit
    in the unfilled buffer, the buffer is flushed ragged — so the system
    cannot deadlock even when all ``video_workers`` threads close at once
    with a part-filled group.
  - ``depth`` bounds un-materialized device groups, same role as
    FeatureStream's depth.
  - A group that fails on device (dispatch raises, or the D2H read
    surfaces a runtime error) poisons exactly its member videos: their
    pending counts are released and ``close_video`` re-raises for each,
    so the failure stays per-video (every member is reported failed, the
    rest of the corpus completes) instead of wedging the whole run.
"""
from __future__ import annotations

import threading
from collections import deque
from typing import Any, Dict, List, Optional

import numpy as np

from ..telemetry import trace
from .sequence import blocks_run


class ClipPacker:
    def __init__(self, runner, batch: int, depth: int = 4):
        self.runner = runner
        self.batch = int(batch)
        self.depth = max(int(depth), 1)
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._drain_lock = threading.Lock()     # serializes D2H
        self._dispatch_lock = threading.Lock()  # serializes group dispatch
        # one entry per ROW: (members, stack); a member is (handle, idx,
        # slot): result ``idx`` of ``handle`` is line ``slot`` of the row's
        # output, or the whole of it where slot is None (a clip's row)
        self._buf: List[tuple] = []
        self._inflight: deque = deque()      # [(device_array, manifest, seq)]
        self._results: Dict[int, Dict[int, np.ndarray]] = {}
        self._counts: Dict[int, int] = {}    # clips added per handle
        self._pending: Dict[int, int] = {}   # clips not yet materialized
        self._errors: Dict[int, Exception] = {}  # poisoned-group handles
        self._draining = 0   # groups popped from _inflight, D2H not done
        self._open = 0
        self._closing = 0
        self._next_handle = 0

    # -- per-video API (each video's decode thread) ------------------------

    def open_video(self) -> int:
        with self._lock:
            h = self._next_handle
            self._next_handle += 1
            self._results[h] = {}
            self._counts[h] = 0
            self._pending[h] = 0
            self._open += 1
            return h

    def add(self, handle: int, stack: np.ndarray) -> None:
        """Append one clip stack; dispatches when the shared group fills."""
        to_dispatch = None
        with self._lock:
            self._raise_if_poisoned(handle)
            self._buf.append(([(handle, self._counts[handle], None)], stack))
            self._counts[handle] += 1
            self._pending[handle] += 1
            to_dispatch = self._take_full_group()
        self._dispatch_and_bound(to_dispatch)

    def _raise_if_poisoned(self, handle: int) -> None:
        """Under the lock: an earlier group containing this video's clips
        already failed, so stop the video now (the caller's except-path
        aborts it) instead of decoding and dispatching clips whose only
        possible outcome is a close_video failure."""
        err = self._errors.get(handle)
        if err is not None:
            raise RuntimeError(
                "a packed clip group containing this video's clips failed "
                f"on device: {err}") from err

    def _take_full_group(self) -> Optional[List[tuple]]:
        """Under the lock: the buffered rows, if they fill a group."""
        group = None
        if len(self._buf) >= self.batch:
            group, self._buf = self._buf, []
        trace.counter("packer.buffered", len(self._buf))
        return group

    def _dispatch_and_bound(self, to_dispatch: Optional[List[tuple]]) -> None:
        if to_dispatch is not None:
            # a dispatch failure contains OUR newest clip: propagate so the
            # caller's extractor aborts this video now (members poisoned)
            self._dispatch(to_dispatch)
            with self._lock:
                drain = len(self._inflight) > self.depth
            if drain:
                try:
                    self._drain_oldest()
                except Exception:
                    pass  # the failed group's members are poisoned; each
                    # surfaces at its own close_video, not at this add

    def abort_video(self, handle: int) -> None:
        """Error-path cleanup (per-video isolation): discard the video's
        buffered clips and stop counting it as open. Without this, a video
        that dies after open_video() would leave ``_open`` elevated forever
        and the all-closing flush rule could never fire — wedging every
        other worker's close_video. Rows of its already-dispatched clips
        are dropped at drain time (the results entry is gone)."""
        with self._lock:
            self._drop_buffered(handle)
            self._results.pop(handle, None)
            self._counts.pop(handle, None)
            self._pending.pop(handle, None)
            self._errors.pop(handle, None)
            self._open -= 1
            self._cond.notify_all()

    def _drop_buffered(self, handle: int) -> None:
        """Under the lock: forget an aborted video's rows not yet sent."""
        self._buf = [e for e in self._buf if e[0][0][0] != handle]

    def _seal_open_row(self) -> None:
        """Under the lock, when every open video is closing: move what a
        subclass still assembles into ``_buf``. Clips are whole rows."""

    def close_video(self, handle: int) -> np.ndarray:
        """Block until every clip of ``handle`` materialized; return the
        (n_clips, ...) feature rows in add order."""
        with self._lock:
            self._closing += 1
        try:
            while True:
                to_flush = None
                with self._lock:
                    # pending counts buffered AND in-flight clips, so zero
                    # means everything of ours has materialized. A poisoned
                    # handle breaks out regardless of the count — the error
                    # (raised below) is the result, and waiting on counts a
                    # failed drain may not have balanced would hang instead
                    # of surfacing it.
                    if self._pending[handle] == 0 or handle in self._errors:
                        break
                    # a group some thread is copying out still occupies
                    # the device's queue: a ragged flush behind it would
                    # only send what has arrived so far on its own (one
                    # document a dispatch in a served loop, PERF.md
                    # section 7); wait for that copy at the drain lock
                    # below, and flush what has gathered by then
                    if not self._inflight and not self._draining:
                        if self._closing >= self._open:
                            self._seal_open_row()
                        if self._buf and self._closing >= self._open:
                            # every open video is closing: nobody will fill
                            # the group — flush it ragged (the only ragged
                            # dispatch in the system)
                            to_flush, self._buf = self._buf, []
                            trace.counter("packer.ragged_flush",
                                          len(to_flush))
                            trace.counter("packer.buffered", 0)
                        else:
                            # other videos are still decoding; their adds
                            # will fill the buffer. The timeout guards the
                            # race where the last feeder transitions to
                            # closing between our check and the wait.
                            with trace.span("packer.fill_wait"):
                                self._cond.wait(timeout=0.05)
                            continue
                if to_flush is not None:
                    try:
                        self._dispatch(to_flush)
                    except Exception:
                        continue  # members poisoned; ours surfaces below
                try:
                    self._drain_oldest()
                except Exception:
                    pass  # poisoned members (possibly us) surface below
        finally:
            with self._lock:
                self._closing -= 1
                self._open -= 1
                rows = self._results.pop(handle)
                n = self._counts.pop(handle)
                self._pending.pop(handle)
                err = self._errors.pop(handle, None)
        if err is not None:
            raise RuntimeError(
                "a packed clip group containing this video's clips failed "
                f"on device: {err}") from err
        if n == 0:
            return np.empty((0,), np.float32)
        with trace.span("batch.collect", rows=n):
            return np.stack([rows[i] for i in range(n)])

    # -- internals ---------------------------------------------------------

    def _dispatch(self, items: List[tuple]) -> None:
        """Stack + enqueue a group WITHOUT the main lock held (the host
        copy of a B=128 group is tens of MB — holding the lock there would
        stall every decode thread). The dispatch lock keeps the inflight
        order consistent with dispatch order."""
        with trace.span("packer.lock_wait", lock="dispatch"):
            self._dispatch_lock.acquire()
        try:
            manifest = [members for members, _ in items]
            try:
                # np.stack inside the try: a shape mismatch or MemoryError
                # here has already consumed the clips from _buf, so it must
                # poison the members exactly like a device failure
                with trace.span("packer.stack", rows=len(items)):
                    group = np.stack([s for _, s in items])
                dev = self.runner.dispatch(group)
                seq = getattr(self.runner, "last_seq", None)
            except Exception as e:
                self._poison(manifest, e)
                raise
            with self._lock:
                self._inflight.append((dev, manifest, seq))
                self._cond.notify_all()
        finally:
            self._dispatch_lock.release()

    def _poison(self, manifest, exc: Exception) -> None:
        """A group died on device: release its members' pending counts and
        record the error so each member's ``close_video`` raises instead of
        spinning forever on clips that will never materialize."""
        with self._lock:
            for members in manifest:
                for h, _idx, _slot in members:
                    if h in self._pending:
                        self._pending[h] -= 1
                        self._errors[h] = exc
            self._cond.notify_all()

    def _drain_oldest(self) -> None:
        """Materialize the oldest in-flight group (if any) and route its
        rows to their videos. D2H happens outside the main lock so decode
        threads keep feeding; the drain lock keeps materialization
        submit-ordered."""
        # another worker may be inside the blocking D2H below: standing at
        # this lock is a stall on the device like its `forward`, one removed
        with trace.span("packer.lock_wait", lock="drain"):
            self._drain_lock.acquire()
        try:
            with self._lock:
                if not self._inflight:
                    return
                dev, manifest, seq = self._inflight.popleft()
                self._draining += 1
            # ANY failure after the pop (the blocking D2H is the expected
            # one, but also e.g. a routing bug below) must poison the
            # members — once the group left _inflight, nobody else can
            # materialize it, and un-poisoned members would spin in
            # close_video forever instead of surfacing the error
            try:
                from ..utils.profiling import profiler
                # same stage contract as FeatureStream._pop: under async
                # dispatch this is the host's *stall* time on the device,
                # which is what the per-stage roofline breakdown
                # (trace_report / bench_pipeline) needs attributed —
                # without it a packed run's device time is invisible
                with profiler.stage("forward"), \
                        trace.span("mesh.fetch", seq=seq):
                    host = np.asarray(dev)  # blocking D2H
                with trace.span("packer.route", rows=len(manifest)), \
                        self._lock:
                    for row, members in enumerate(manifest):
                        for h, idx, slot in members:
                            if h in self._results:
                                self._results[h][idx] = host[row] \
                                    if slot is None else host[row, slot]
                                self._pending[h] -= 1
                    self._cond.notify_all()
            except Exception as e:
                self._poison(manifest, e)
                raise
            finally:
                with self._lock:
                    self._draining -= 1
                    self._cond.notify_all()
        finally:
            self._drain_lock.release()


class SegmentPacker(ClipPacker):
    """Rows of ``row_len`` tokens filled with segments from several
    documents, then packed into groups as clips are.

    A segment is one window of a document, at most ``row_len`` token ids. It
    goes into the open row behind the segments already there, under the next
    segment id (1, 2, ...; 0 is padding), so a row is ``(2, row_len) int32``:
    ids and segment ids, each segment a contiguous run. A row is sealed
    when the next segment does not fit or it holds ``max_segments``; the
    runner returns one line per segment id for every row, and line ``s - 1``
    goes back to the document that owns segment ``s``. First fit into the one
    open row only: documents arrive in no order worth sorting for.
    """

    def __init__(self, runner, batch: int, row_len: int, max_segments: int,
                 depth: int = 4):
        super().__init__(runner, batch, depth)
        self.row_len = int(row_len)
        self.max_segments = int(max_segments)
        self._row: List[tuple] = []     # [(handle, idx, tokens)] of the open row
        self._row_fill = 0

    def add(self, handle: int, tokens: np.ndarray) -> None:
        """Append one segment; seals the open row when it does not fit and
        dispatches when the sealed rows fill a group."""
        tokens = np.asarray(tokens, np.int32)
        if not 0 < len(tokens) <= self.row_len:
            raise ValueError(f"a segment of {len(tokens)} tokens for rows "
                             f"of {self.row_len}")
        with self._lock:
            self._raise_if_poisoned(handle)
            if self._row_fill + len(tokens) > self.row_len \
                    or len(self._row) >= self.max_segments:
                self._seal_open_row()
            self._row.append((handle, self._counts[handle], tokens))
            self._row_fill += len(tokens)
            self._counts[handle] += 1
            self._pending[handle] += 1
            if self._row_fill == self.row_len:
                self._seal_open_row()
            to_dispatch = self._take_full_group()
        self._dispatch_and_bound(to_dispatch)

    def _seal_open_row(self) -> None:
        if not self._row:
            return
        row = np.zeros((2, self.row_len), np.int32)
        at, pairs, members = 0, 0, []
        for slot, (handle, idx, tokens) in enumerate(self._row):
            row[0, at:at + len(tokens)] = tokens
            row[1, at:at + len(tokens)] = slot + 1
            at += len(tokens)
            pairs += len(tokens) * (len(tokens) + 1) // 2
            members.append((handle, idx, slot))
        trace.counter("packer.row_fill", at, series="tokens")
        trace.counter("packer.row_fill", self.row_len, series="capacity")
        # the causal same-document (query, key) pairs of the row over all an
        # attention that skips nothing computes
        trace.counter("packer.pair_fill", pairs, series="pairs")
        trace.counter("packer.pair_fill", self.row_len ** 2,
                      series="capacity")
        if trace.active() is not None:
            # ... and the (query tile, key block) pairs the token families'
            # attention runs on this row, of all the row has
            kept, total = blocks_run(row[1:])
            trace.counter("attention.blocks", kept, series="kept")
            trace.counter("attention.blocks", total, series="total")
        self._buf.append((members, row))
        self._row, self._row_fill = [], 0

    def _drop_buffered(self, handle: int) -> None:
        """An aborted document's segments leave the open row; a sealed row
        is sent as it is (its lines for the aborted document are dropped
        when they come back)."""
        self._row = [e for e in self._row if e[0] != handle]
        self._row_fill = sum(len(e[2]) for e in self._row)
