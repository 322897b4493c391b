"""Fault-tolerance runtime: taxonomy, retry policy, deadlines, journal.

The reference's entire failure story is "print a traceback and move on"
(reference models/_base/base_extractor.py:40-53) — acceptable for a
workstation run, not for a preemptible TPU fleet where a long video costs
minutes of compute (RAFT, arXiv:2003.12039) and a single hung decode
stalls a worker thread forever. This module gives the extraction loop
four properties the ROADMAP north star needs:

  1. **Taxonomy** (:func:`classify`): every per-video failure is
     ``TRANSIENT`` (ffmpeg blip, OOM-killed decode worker, NFS hiccup —
     worth retrying), ``POISON`` (the input itself is bad — bounded
     retries, then quarantine) or ``FATAL`` (config/programming error —
     retrying cannot help; fail the video immediately, keep the run's
     per-video isolation).
  2. **Retry policy** (:class:`RetryPolicy`): bounded attempts with
     exponential backoff + jitter, configured by the ``retry_attempts=``
     / ``retry_backoff_s=`` config keys. Clock/sleep/rng are injectable
     so tier-1 tests never really sleep.
  3. **Per-video deadline** (:class:`FaultContext`): ``video_deadline_s=``
     arms a watchdog timer that cancels every registered in-flight video
     source (thread-safe ``cancel()`` on VideoSource /
     ProcessVideoSource / ParallelVideoSource, utils/io.py) so a hung
     decode fails ONLY that video — the worker thread comes back and the
     rest of the run proceeds.
  4. **Failure journal** (:class:`FailureJournal`):
     ``{output_path}/_failures.jsonl``, one atomically-appended record
     per terminal failure. A restarted worker consults it to skip
     known-POISON inputs instead of re-failing them (override with
     ``retry_failed=true``); the end-of-run summary tallies categories.

The **decode degradation ladder** also lives here (:data:`LADDER`,
:func:`demote`): when a video fails under ``video_decode=parallel`` or
``process``, the retry runs it with the next-simpler source
(``parallel -> process -> inline``) via the thread-local context's
``decode_override``, which ``BaseExtractor.video_source`` honors.
"""
from __future__ import annotations

import errno
import json
import os
import random
import socket
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional, Union

# -- taxonomy ---------------------------------------------------------------

TRANSIENT = "TRANSIENT"  # environment blip: retry with backoff
POISON = "POISON"        # the input is bad: bounded retries, then quarantine
FATAL = "FATAL"          # config/programming error: retrying cannot help

CATEGORIES = (TRANSIENT, POISON, FATAL)


class DeadlineExceeded(Exception):
    """Raised (by a cancelled video source) when the per-video wall-clock
    deadline kills an in-flight decode. Classified TRANSIENT: a hung
    decode is usually an NFS/network stall, and the retry additionally
    walks the decode ladder toward simpler sources."""


class PoisonError(Exception):
    """Explicitly mark an input-is-bad failure (classify -> POISON)."""


class FatalError(Exception):
    """Explicitly mark a do-not-retry failure (classify -> FATAL)."""


#: substrings of worker-forwarded error strings (the decode subprocess
#: protocol ships ``f"{type(e).__name__}: {e}"``, utils/io.py) that mark
#: the CHILD's exception as input-shaped
_POISON_MARKERS = ("ValueError", "PoisonError", "NonFiniteFeatureError",
                   "No decodable frames", "Cannot determine fps")

#: OSError errnos that mean the ENVIRONMENT cannot take writes at all —
#: full disk, exceeded quota, read-only remount. Retrying burns the whole
#: retry budget plus backoff wall-clock per video and every video fails
#: the same way, turning one full disk into a slow fleet-wide hang; fail
#: the video immediately so the operator sees N fast FATALs, not a crawl
_FATAL_ERRNOS = frozenset({
    getattr(errno, name) for name in ("ENOSPC", "EDQUOT", "EROFS")
    if hasattr(errno, name)
})

#: the same verdict for worker-FORWARDED errors: the decode subprocess
#: protocol ships strings, and str(OSError) keeps the strerror
_FATAL_MARKERS = ("ENOSPC", "EDQUOT", "EROFS", "No space left on device",
                  "Disk quota exceeded", "Read-only file system")


def classify(exc: BaseException) -> str:
    """Map an exception to TRANSIENT / POISON / FATAL.

    Unknown exceptions default to TRANSIENT: a wrong TRANSIENT costs a few
    bounded retries; a wrong POISON quarantines a healthy video and a
    wrong FATAL skips retries that might have worked.
    """
    if isinstance(exc, DeadlineExceeded):
        return TRANSIENT
    if isinstance(exc, FatalError):
        return FATAL
    if isinstance(exc, PoisonError):
        return POISON
    from ..telemetry.health import NonFiniteFeatureError
    if isinstance(exc, NonFiniteFeatureError):
        # the output-health gate (telemetry/health.py, health=true) found
        # NaN/Inf in a computed feature: quarantine over silent write —
        # retries rarely fix a numerically-poisoned (input, model) pair
        return POISON
    if isinstance(exc, (NotImplementedError, AssertionError, TypeError,
                        AttributeError, NameError, ImportError)):
        # config/programming errors: these would fail every retry (and
        # likely every other video) identically
        return FATAL
    if isinstance(exc, (ValueError, KeyError, IndexError)):
        # cv2-can't-open / no-frames / bad-fps all surface as ValueError
        # (utils/io.py get_video_props, count_frames_by_decode)
        return POISON
    if type(exc).__module__ == "cv2":
        return POISON  # codec/container rejection of this input
    if isinstance(exc, RuntimeError):
        msg = str(exc)
        if "died without a result" in msg:
            return TRANSIENT  # OOM-SIGKILLed decode worker (utils/io.py)
        if any(m in msg for m in _POISON_MARKERS):
            return POISON  # worker-forwarded child exception, by name
        if any(m in msg for m in _FATAL_MARKERS):
            return FATAL  # forwarded full-disk/quota/read-only verdicts
        return TRANSIENT  # spawn failures, queue breakage, ffmpeg blips
    if isinstance(exc, OSError):
        if exc.errno in _FATAL_ERRNOS:
            # full disk / quota / read-only: retrying cannot help and every
            # other video fails identically — fail fast, keep isolation
            return FATAL
        return TRANSIENT  # NFS hiccup / EIO blip / URLError
    if isinstance(exc, MemoryError):
        return TRANSIENT  # host memory pressure may clear
    return TRANSIENT


# -- decode degradation ladder ---------------------------------------------

#: most- to least-parallel decode source; demotion walks rightward
LADDER = ("parallel", "process", "inline")


def demote(mode: Optional[str]) -> Optional[str]:
    """Next-simpler decode mode, or None when already at (or past)
    ``inline``."""
    if mode not in LADDER:
        return None
    i = LADDER.index(mode)
    return LADDER[i + 1] if i + 1 < len(LADDER) else None


# -- retry policy -----------------------------------------------------------

@dataclass
class RetryPolicy:
    """Bounded-retry parameters plus injectable time sources.

    ``attempts`` counts TOTAL tries per video (1 = the reference's
    single-shot behavior). ``backoff_delay(k)`` is the sleep AFTER failed
    attempt ``k`` (1-based): ``backoff_s * 2**(k-1)``, capped, with
    uniform jitter in ``[0, jitter * base]`` so a restarted fleet does
    not retry in lockstep against the same NFS server.
    """
    attempts: int = 1
    backoff_s: float = 0.5
    backoff_cap_s: float = 30.0
    jitter: float = 0.1
    deadline_s: Optional[float] = None
    ladder: bool = True  # demote video_decode on retries
    retry_failed: bool = False  # re-run journal-quarantined inputs
    sleep: Callable[[float], None] = time.sleep
    clock: Callable[[], float] = time.monotonic
    rng: random.Random = field(default_factory=random.Random)

    def __post_init__(self):
        if int(self.attempts) < 1:
            raise ValueError(f"retry_attempts={self.attempts}: need >= 1")
        if float(self.backoff_s) < 0:
            raise ValueError(f"retry_backoff_s={self.backoff_s}: need >= 0")
        if self.deadline_s is not None and float(self.deadline_s) <= 0:
            raise ValueError(
                f"video_deadline_s={self.deadline_s}: need > 0 (or null)")
        self.attempts = int(self.attempts)

    @classmethod
    def from_config(cls, args) -> "RetryPolicy":
        """Build from the ``retry_attempts`` / ``retry_backoff_s`` /
        ``video_deadline_s`` / ``retry_failed`` config keys (all 8
        ``configs/*.yml`` carry them)."""
        attempts = args.get("retry_attempts")
        backoff = args.get("retry_backoff_s")
        deadline = args.get("video_deadline_s")
        return cls(
            attempts=1 if attempts is None else int(attempts),
            backoff_s=0.5 if backoff is None else float(backoff),
            deadline_s=None if deadline is None else float(deadline),
            retry_failed=bool(args.get("retry_failed", False)),
        )

    def backoff_delay(self, failed_attempt: int) -> float:
        base = min(float(self.backoff_s) * (2.0 ** (failed_attempt - 1)),
                   float(self.backoff_cap_s))
        return base * (1.0 + float(self.jitter) * self.rng.random())


# -- per-video fault context (deadline watchdog + ladder override) ----------

_tls = threading.local()


def current_context() -> Optional["FaultContext"]:
    """The FaultContext of the video attempt running on THIS thread, if
    any (``BaseExtractor.video_source`` registers its sources here)."""
    return getattr(_tls, "ctx", None)


class FaultContext:
    """One extraction attempt of one video: deadline watchdog + the
    decode-ladder override, installed thread-locally for the duration.

    The watchdog is a daemon :class:`threading.Timer`; at
    ``deadline_s`` it calls ``cancel()`` on every registered source.
    Cancellation is cooperative: an in-thread source (``VideoSource``,
    the shared ``FrameBus``) is only flagged — the thread that opened the
    capture sees the flag before its next frame, raises
    :class:`DeadlineExceeded` from its ``frames()`` loop and releases its
    own capture, so only THIS video fails and the worker thread survives.
    No capture is released under a running read (cv2 deadlocks or aborts
    there). A decoder stuck inside one call is bounded by
    ``video_decode=process``, whose ``cancel()`` ends a child process.
    """

    def __init__(self, video_path: str, deadline_s: Optional[float] = None,
                 decode_override: Optional[str] = None):
        self.video_path = str(video_path)
        self.deadline_s = deadline_s
        self.decode_override = decode_override
        self.deadline_expired = False
        self._sources: List = []
        self._lock = threading.Lock()
        self._timer: Optional[threading.Timer] = None
        self._prev = None

    # -- source registry ----------------------------------------------------
    def register(self, source) -> None:
        """Track a live video source; cancelled immediately when the
        deadline already fired (a source constructed after expiry must
        not run to completion)."""
        with self._lock:
            expired = self.deadline_expired
            self._sources.append(source)
        if expired:
            self._cancel_source(source)

    def _cancel_source(self, source) -> None:
        try:
            source.cancel(
                f"video deadline ({self.deadline_s}s) exceeded for "
                f"{self.video_path}")
        except Exception:
            pass  # watchdog must never die on a half-torn-down source

    def _expire(self) -> None:
        with self._lock:
            self.deadline_expired = True
            sources = list(self._sources)
        print(f"WATCHDOG: {self.video_path} exceeded video_deadline_s="
              f"{self.deadline_s}; cancelling its in-flight decode "
              f"({len(sources)} source(s))")
        from .. import telemetry
        telemetry.inc("vft_deadline_expirations_total")
        for s in sources:
            self._cancel_source(s)

    # -- context manager ----------------------------------------------------
    def __enter__(self) -> "FaultContext":
        self._prev = getattr(_tls, "ctx", None)
        _tls.ctx = self
        if self.deadline_s is not None:
            self._timer = threading.Timer(float(self.deadline_s),
                                          self._expire)
            self._timer.daemon = True
            self._timer.start()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        _tls.ctx = self._prev
        with self._lock:
            self._sources.clear()


# -- persistent failure journal --------------------------------------------

class FailureJournal:
    """``{output_path}/_failures.jsonl`` — append-only verdicts.

    One JSON record per line: ``{video, category, attempts, error,
    elapsed_s, host, time}``. Appends are single ``os.write`` calls on an
    ``O_APPEND`` fd, so concurrent shard workers sharing the output dir
    never interleave partial lines (POSIX atomic-append for records well
    under PIPE_BUF would require <=4KiB; errors are truncated to keep
    records small). ``load()`` is last-record-wins per video, so a
    later ``RESOLVED`` record (written when ``retry_failed=true``
    succeeds) lifts a quarantine without rewriting history.
    """

    FILENAME = "_failures.jsonl"
    RESOLVED = "RESOLVED"

    def __init__(self, output_path: Union[str, Path]):
        self.path = os.path.join(str(output_path), self.FILENAME)
        self._cache: Optional[Dict[str, dict]] = None
        self._cache_stat: Optional[tuple] = None
        self._lock = threading.Lock()

    # -- writes -------------------------------------------------------------
    def record(self, video: str, category: str, attempts: int, error: str,
               elapsed_s: float) -> dict:
        rec = {
            "video": str(video),
            "category": str(category),
            "attempts": int(attempts),
            "error": str(error)[:1000],
            "elapsed_s": round(float(elapsed_s), 3),
            "host": socket.gethostname(),
            "time": time.time(),
        }
        # serve-mode correlation (telemetry/context.py): stamp the spool
        # request whose video failed; absent in batch runs, so existing
        # journal records and their consumers are untouched
        from ..telemetry.context import current_request_id
        rid = current_request_id()
        if rid is not None:
            rec["request_id"] = rid
        self._append(rec)
        from .. import telemetry
        telemetry.inc("vft_failures_total", category=str(category))
        return rec

    def resolve(self, video: str) -> None:
        """Lift a quarantine: a ``retry_failed=true`` run extracted this
        video successfully, so future runs must not skip it."""
        self._append({"video": str(video), "category": self.RESOLVED,
                      "host": socket.gethostname(), "time": time.time()})

    def _append(self, rec: dict) -> None:
        # single atomic O_APPEND write + torn-tail healing, shared with
        # _telemetry.jsonl (telemetry/jsonl.py — factored out of this
        # class so every JSONL artifact has identical crash semantics)
        from ..telemetry.jsonl import append_jsonl
        append_jsonl(self.path, rec)
        with self._lock:
            self._cache = None  # force re-read after our own write

    # -- reads --------------------------------------------------------------
    def load(self) -> Dict[str, dict]:
        """Per-video latest record. Cached on (mtime, size); corrupt
        lines (a torn append from a killed worker) are skipped, never
        fatal — the journal is an optimization, not a lock."""
        try:
            st = os.stat(self.path)
            stat_key = (st.st_mtime_ns, st.st_size)
        except OSError:
            return {}
        with self._lock:
            if self._cache is not None and self._cache_stat == stat_key:
                return self._cache
        out: Dict[str, dict] = {}
        try:
            with open(self.path, encoding="utf-8", errors="replace") as f:
                for raw in f:
                    raw = raw.strip()
                    if not raw:
                        continue
                    try:
                        rec = json.loads(raw)
                    except (json.JSONDecodeError, ValueError):
                        continue
                    if isinstance(rec, dict) and "video" in rec:
                        out[str(rec["video"])] = rec
        except OSError:
            return {}
        with self._lock:
            self._cache, self._cache_stat = out, stat_key
        return out

    def poison_record(self, video: str) -> Optional[dict]:
        """This video's latest record iff it quarantines (category
        POISON); RESOLVED / TRANSIENT / FATAL records do not — transient
        and fatal terminal failures are re-attempted by a restarted
        worker (the environment or config may have changed)."""
        rec = self.load().get(str(video))
        if rec is not None and rec.get("category") == POISON:
            return rec
        return None

    def tally_by_category(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for rec in self.load().values():
            cat = rec.get("category", "?")
            if cat != self.RESOLVED:
                out[cat] = out.get(cat, 0) + 1
        return out
