"""Fault-tolerance runtime: taxonomy, backoff, deadline watchdog, decode
degradation ladder, persistent failure journal (utils/faults.py +
sinks.safe_extract).

Tier-1 discipline: the retry tests inject ``sleep``/``clock`` so no real
backoff is ever slept; the watchdog tests use sub-second deadlines.
"""
import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from video_features_tpu.utils import faults, sinks
from video_features_tpu.utils.faults import (FailureJournal, FaultContext,
                                             DeadlineExceeded, RetryPolicy)

pytestmark = pytest.mark.quick

REPO = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------- taxonomy

@pytest.mark.parametrize("exc,want", [
    (DeadlineExceeded("v: deadline"), faults.TRANSIENT),
    (OSError("NFS hiccup"), faults.TRANSIENT),
    (MemoryError(), faults.TRANSIENT),
    (RuntimeError("decode worker for v died without a result (killed?)"),
     faults.TRANSIENT),
    (RuntimeError("spawn failed"), faults.TRANSIENT),
    (ValueError("Cannot determine fps of v.mp4"), faults.POISON),
    (ValueError("No decodable frames in v.mp4"), faults.POISON),
    (RuntimeError("decode worker failed for v: ValueError: bad header"),
     faults.POISON),
    (faults.PoisonError("marked"), faults.POISON),
    (NotImplementedError("on_extraction: bogus"), faults.FATAL),
    (AssertionError("stack_size"), faults.FATAL),
    (TypeError("bad transform"), faults.FATAL),
    (faults.FatalError("marked"), faults.FATAL),
])
def test_classify(exc, want):
    assert faults.classify(exc) == want


def test_classify_unknown_defaults_transient():
    class Weird(Exception):
        pass
    assert faults.classify(Weird("?")) == faults.TRANSIENT


def test_classify_disk_full_errnos_are_fatal():
    """ENOSPC/EDQUOT/EROFS must classify FATAL, not TRANSIENT: retrying
    a full disk burns the whole retry budget plus backoff wall-clock per
    video — one full disk would otherwise become a slow fleet-wide hang
    (ISSUE 9 satellite). A plain EIO stays TRANSIENT (NFS blips clear)."""
    import errno
    for code in ("ENOSPC", "EDQUOT", "EROFS"):
        exc = OSError(getattr(errno, code), f"synthetic {code}")
        assert faults.classify(exc) == faults.FATAL, code
    assert faults.classify(OSError(errno.EIO, "blip")) == faults.TRANSIENT
    assert faults.classify(OSError("errno-less oserror")) == faults.TRANSIENT


def test_classify_forwarded_disk_full_is_fatal():
    """The decode-worker protocol forwards child exceptions as strings
    (utils/io.py, parallel/fanout.py); str(OSError) keeps the strerror,
    and the forwarded form must reach the same FATAL verdict."""
    fwd = RuntimeError("OSError: [Errno 28] No space left on device: 'x'")
    assert faults.classify(fwd) == faults.FATAL
    fwd = RuntimeError("shared decode failed for v.mp4: OSError: "
                       "[Errno 122] Disk quota exceeded")
    assert faults.classify(fwd) == faults.FATAL
    # an injected-EIO forwarded error must NOT harden into FATAL
    fwd = RuntimeError("OSError: [Errno 5] injected EIO at decode.read")
    assert faults.classify(fwd) == faults.TRANSIENT


def test_ladder_order():
    assert faults.demote("parallel") == "process"
    assert faults.demote("process") == "inline"
    assert faults.demote("inline") is None
    assert faults.demote(None) is None


# ----------------------------------------------------------- retry policy

def test_backoff_schedule_doubles_and_caps():
    pol = RetryPolicy(attempts=6, backoff_s=0.5, backoff_cap_s=3.0,
                      jitter=0.0)
    assert [pol.backoff_delay(k) for k in range(1, 6)] == \
        [0.5, 1.0, 2.0, 3.0, 3.0]


def test_backoff_jitter_bounds():
    pol = RetryPolicy(attempts=2, backoff_s=1.0, jitter=0.25)
    delays = [pol.backoff_delay(1) for _ in range(50)]
    assert all(1.0 <= d <= 1.25 for d in delays)
    assert len(set(delays)) > 1  # actually jittered

def test_policy_from_config_and_validation():
    pol = RetryPolicy.from_config({})
    assert pol.attempts == 1 and pol.deadline_s is None
    pol = RetryPolicy.from_config(
        {"retry_attempts": 4, "retry_backoff_s": 0.1,
         "video_deadline_s": 30, "retry_failed": True})
    assert (pol.attempts, pol.backoff_s, pol.deadline_s,
            pol.retry_failed) == (4, 0.1, 30.0, True)
    with pytest.raises(ValueError):
        RetryPolicy(attempts=0)
    with pytest.raises(ValueError):
        RetryPolicy(deadline_s=0)


def test_transient_failure_recovers_on_retry(capsys):
    """Injected transient decode failures succeed on retry; the backoff
    schedule is honored (injected sleep — no real waiting) and the
    success path reports the attempt count (journal-free)."""
    sleeps = []
    pol = RetryPolicy(attempts=3, backoff_s=0.5, jitter=0.0,
                      sleep=sleeps.append, clock=lambda: 0.0)
    calls = []

    def flaky(path):
        calls.append(path)
        if len(calls) < 3:
            raise OSError("ffmpeg blip")
        return {"x": 1}

    assert sinks.safe_extract(flaky, "v.mp4", policy=pol) == "done"
    assert len(calls) == 3
    assert sleeps == [0.5, 1.0]
    assert 'Recovered "v.mp4" on attempt 3/3' in capsys.readouterr().out


def test_poison_quarantined_after_exact_attempts(tmp_path):
    """A poison input is retried exactly ``retry_attempts`` times, then
    journaled with category=POISON; a restarted worker skips it without
    calling the extractor; retry_failed=true re-runs it and a success
    lifts the quarantine."""
    journal = FailureJournal(tmp_path)
    pol = RetryPolicy(attempts=3, backoff_s=0.0, jitter=0.0,
                      sleep=lambda s: None, clock=lambda: 0.0)
    calls = []

    def poison(path):
        calls.append(path)
        raise ValueError(f"Cannot determine fps of {path}")

    assert sinks.safe_extract(poison, "bad.mp4", policy=pol,
                              journal=journal) == "error"
    assert len(calls) == 3

    recs = [json.loads(l) for l in open(journal.path) if l.strip()]
    assert len(recs) == 1
    rec = recs[0]
    assert rec["video"] == "bad.mp4"
    assert rec["category"] == faults.POISON
    assert rec["attempts"] == 3
    assert "Cannot determine fps" in rec["error"]
    assert rec["host"]  # hostname recorded for fleet triage
    assert "elapsed_s" in rec

    # restart: known-poison input is skipped, extractor never called
    assert sinks.safe_extract(poison, "bad.mp4", policy=pol,
                              journal=journal) == "quarantined"
    assert len(calls) == 3

    # retry_failed=true: re-runs; success appends RESOLVED (last wins)
    pol_rf = RetryPolicy(attempts=1, retry_failed=True)
    assert sinks.safe_extract(lambda p: {"x": 1}, "bad.mp4", policy=pol_rf,
                              journal=journal) == "done"
    assert journal.poison_record("bad.mp4") is None
    assert sinks.safe_extract(lambda p: {"x": 1}, "bad.mp4", policy=pol,
                              journal=journal) == "done"  # stays lifted


def test_fatal_fails_without_retry(tmp_path):
    journal = FailureJournal(tmp_path)
    pol = RetryPolicy(attempts=5, backoff_s=0.0, sleep=lambda s: None,
                      clock=lambda: 0.0)
    calls = []

    def broken_config(path):
        calls.append(path)
        raise NotImplementedError("resize='bogus'")

    assert sinks.safe_extract(broken_config, "v.mp4", policy=pol,
                              journal=journal) == "error"
    assert len(calls) == 1  # retrying a config error cannot help
    rec = journal.load()["v.mp4"]
    assert rec["category"] == faults.FATAL and rec["attempts"] == 1
    # FATAL terminal records do NOT quarantine on resume (the config may
    # have been fixed between runs)
    assert journal.poison_record("v.mp4") is None


def test_transient_terminal_failure_does_not_quarantine(tmp_path):
    journal = FailureJournal(tmp_path)
    pol = RetryPolicy(attempts=2, backoff_s=0.0, sleep=lambda s: None,
                      clock=lambda: 0.0)
    calls = []

    def down(path):
        calls.append(path)
        raise OSError("mount gone")

    assert sinks.safe_extract(down, "v.mp4", policy=pol,
                              journal=journal) == "error"
    assert journal.load()["v.mp4"]["category"] == faults.TRANSIENT
    # a restarted worker re-attempts it (the environment may be healthy)
    assert sinks.safe_extract(down, "v.mp4", policy=pol,
                              journal=journal) == "error"
    assert len(calls) == 4


def test_default_policy_matches_legacy_single_shot():
    calls = []

    def bad(path):
        calls.append(path)
        raise RuntimeError("decode failed")

    assert sinks.safe_extract(bad, "v.mp4") == "error"
    assert calls == ["v.mp4"]
    assert sinks.safe_extract(lambda p: {"x": 1}, "v.mp4") == "done"
    assert sinks.safe_extract(lambda p: None, "v.mp4") == "skipped"


# ---------------------------------------------------------------- journal

def test_journal_atomic_append_and_corrupt_line_tolerance(tmp_path):
    journal = FailureJournal(tmp_path)
    journal.record("a.mp4", faults.POISON, 3, "bad", 1.0)
    # a torn append from a SIGKILLed worker must not poison the reader
    with open(journal.path, "a") as f:
        f.write('{"video": "torn.mp4", "categ')
    journal2 = FailureJournal(tmp_path)  # fresh reader (restart)
    loaded = journal2.load()
    assert set(loaded) == {"a.mp4"}
    assert journal2.poison_record("a.mp4")["attempts"] == 3
    # appends still line-atomic afterwards
    journal2.record("b.mp4", faults.TRANSIENT, 1, "x", 0.1)
    assert set(FailureJournal(tmp_path).load()) == {"a.mp4", "b.mp4"}


def test_journal_last_record_wins(tmp_path):
    journal = FailureJournal(tmp_path)
    journal.record("v.mp4", faults.TRANSIENT, 1, "first", 0.1)
    journal.record("v.mp4", faults.POISON, 3, "second", 0.2)
    assert journal.load()["v.mp4"]["error"] == "second"
    assert journal.poison_record("v.mp4") is not None
    journal.resolve("v.mp4")
    assert journal.poison_record("v.mp4") is None
    assert journal.tally_by_category() == {}  # RESOLVED not tallied


def test_journal_missing_file_is_empty(tmp_path):
    journal = FailureJournal(tmp_path / "nonexistent")
    assert journal.load() == {}
    assert journal.poison_record("v.mp4") is None


# ------------------------------------------------------- deadline watchdog

def test_deadline_kills_hung_video_and_run_continues(tmp_path):
    """Acceptance: a deliberately hung decode is killed by
    video_deadline_s while the remaining videos in the same run complete
    successfully — the worker thread survives, only the hung video fails,
    and its journal record says so."""
    journal = FailureJournal(tmp_path)
    pol = RetryPolicy(attempts=1, deadline_s=0.2)

    class _HangingSource:
        """Stands in for a decode blocked inside cv2: only the
        watchdog's cancel() can unblock it."""

        def __init__(self):
            self.unblocked = threading.Event()
            self.reason = None

        def cancel(self, reason=""):
            self.reason = reason
            self.unblocked.set()

    def extract(path):
        if path == "hang.mp4":
            src = _HangingSource()
            faults.current_context().register(src)
            assert src.unblocked.wait(timeout=10), "watchdog never fired"
            raise DeadlineExceeded(src.reason)
        return {"ok": np.ones(1)}

    t0 = time.monotonic()
    statuses = [sinks.safe_extract(extract, v, policy=pol, journal=journal)
                for v in ("a.mp4", "hang.mp4", "c.mp4")]
    assert statuses == ["done", "error", "done"]
    assert time.monotonic() - t0 < 5.0  # killed at ~0.2s, not hung
    rec = journal.load()["hang.mp4"]
    assert rec["category"] == faults.TRANSIENT
    assert "deadline" in rec["error"]


def test_deadline_cancels_real_videosource(sample_video):
    """The watchdog's thread-safe cancel() on a live VideoSource makes
    the iterating thread raise DeadlineExceeded instead of yielding a
    silently-truncated stream."""
    from video_features_tpu.utils.io import VideoSource
    src = VideoSource(sample_video, batch_size=4)
    n = 0
    with FaultContext("v", deadline_s=0.15) as ctx:
        ctx.register(src)
        with pytest.raises(DeadlineExceeded):
            for batch, _, _ in src:
                n += len(batch)
                time.sleep(0.01)  # a slow consumer; decode outlives 0.15s
    assert 0 < n < 355  # genuinely interrupted mid-video


# run in a child: what a cancel under a running read did at PR 33 (abort in
# libavcodec, or a deadlock inside cv2) must not take the test worker along
_CANCEL_ROUNDS = r"""
import random, sys, threading, time
from video_features_tpu.parallel.fanout import FrameBus
from video_features_tpu.utils.faults import DeadlineExceeded
from video_features_tpu.utils.io import VideoSource

what, path, rounds = sys.argv[1], sys.argv[2], int(sys.argv[3])
rng = random.Random(0)


def run_round():
    ended = []

    def drain(source, again):
        try:
            frames = source().frames
            while True:
                for _ in frames():
                    pass
                if not again:
                    break
            ended.append("end")
        except DeadlineExceeded:
            ended.append("deadline")

    if what == "source":
        # one pass takes 0.2 s: go round until the cancel lands
        target = src = VideoSource(path)
        threads = [threading.Thread(target=drain, args=(lambda: src, True))]
    else:
        target = bus = FrameBus(path, ["a", "b"])
        threads = [threading.Thread(
            target=drain, args=(lambda n=n: bus.subscribe(n), False))
            for n in ("a", "b")]
    for t in threads:
        t.start()
    if what == "bus":
        # the pass starts once both have subscribed, on a thread of its own
        while bus._thread is None:
            time.sleep(0.001)
        threads.append(bus._thread)
    time.sleep(rng.uniform(0.0, 0.02))
    target.cancel("round over")
    for t in threads:
        t.join(5)
        assert not t.is_alive(), f"{t.name} still running 5 s after cancel"
    return ended


cancelled = attempts = 0
while cancelled < rounds:
    attempts += 1
    # a bus whose one pass was over before the cancel is not a round
    assert attempts <= 2 * rounds, (cancelled, attempts)
    ended = run_round()
    assert what == "bus" or ended == ["deadline"], ended
    cancelled += set(ended) == {"deadline"}
print(f"{what}: {cancelled} rounds cancelled in {attempts}")
"""


@pytest.mark.parametrize("what,rounds", [("source", 200), ("bus", 20)])
def test_cancel_under_a_running_read(sample_video, what, rounds):
    """cancel() from another thread while the owner decodes flat out:
    every round ends in DeadlineExceeded within 5 s and the process lives
    (a VideoSource, and a FrameBus with two subscribers: a subscriber
    polls its queue once a second, so a bus round costs a second and 20
    are run). At PR 33 cancel() released the capture under the read and
    either count aborted or stood still, six runs of six."""
    done = subprocess.run(
        [sys.executable, "-c", _CANCEL_ROUNDS, what, str(sample_video),
         str(rounds)],
        cwd=REPO, capture_output=True, text=True, timeout=240)
    assert done.returncode == 0, (done.returncode, done.stdout[-2000:],
                                  done.stderr[-2000:])
    assert f"{what}: {rounds} rounds cancelled" in done.stdout


def test_register_after_expiry_cancels_immediately():
    cancelled = []

    class _Src:
        def cancel(self, reason=""):
            cancelled.append(reason)

    with FaultContext("v", deadline_s=0.05) as ctx:
        deadline = time.monotonic() + 5
        while not ctx.deadline_expired and time.monotonic() < deadline:
            time.sleep(0.01)
        assert ctx.deadline_expired
        ctx.register(_Src())  # constructed after the deadline fired
    assert len(cancelled) == 1


def test_context_is_thread_local_and_restored():
    assert faults.current_context() is None
    with FaultContext("outer") as outer:
        assert faults.current_context() is outer
        with FaultContext("inner") as inner:
            assert faults.current_context() is inner
        assert faults.current_context() is outer
        seen = []
        t = threading.Thread(
            target=lambda: seen.append(faults.current_context()))
        t.start()
        t.join()
        assert seen == [None]  # other threads never see our context
    assert faults.current_context() is None


# -------------------------------------------------- degradation ladder

def test_ladder_process_spawn_failure_degrades_to_inline(
        sample_video, capsys, monkeypatch):
    """A forced ProcessVideoSource spawn failure demotes the retry to
    video_decode=inline via the fault context, and the video succeeds —
    logged loudly (the ladder satellite)."""
    from video_features_tpu.config import Config
    from video_features_tpu.extractors.base import BaseExtractor
    from video_features_tpu.utils import io as io_mod

    class _SpawnBoom:
        def __init__(self, *a, **k):
            raise RuntimeError("spawn failed (injected)")

    monkeypatch.setattr(io_mod, "ProcessVideoSource", _SpawnBoom)

    class _CountingExtractor(BaseExtractor):
        output_feat_keys = ["n"]

        def extract(self, video_path):
            src = self.video_source(video_path, batch_size=64)
            n = sum(len(b) for b, _, _ in src)
            return {"n": np.array([n])}

    args = Config(dict(feature_type="counting", on_extraction="print",
                       tmp_path="tmp", output_path="out", device="cpu",
                       video_decode="process"))
    extractor = _CountingExtractor(args)
    got = {}

    def run(path):
        got["feats"] = extractor.extract(path)
        return got["feats"]

    pol = RetryPolicy(attempts=3, backoff_s=0.0, jitter=0.0,
                      sleep=lambda s: None, clock=lambda: 0.0)
    status = sinks.safe_extract(run, sample_video, policy=pol,
                                decode_mode=extractor.video_decode)
    out = capsys.readouterr().out
    assert status == "done", out
    assert got["feats"]["n"][0] == 355  # the inline retry really decoded
    assert "DECODE LADDER" in out and "video_decode=inline" in out
    assert "Recovered" in out and "attempt 2/3" in out


def test_ladder_disabled_without_decode_mode(monkeypatch, capsys):
    """Library callers that pass no decode_mode get retries but no
    demotion messages (there is nothing to demote)."""
    pol = RetryPolicy(attempts=2, backoff_s=0.0, sleep=lambda s: None,
                      clock=lambda: 0.0)
    calls = []

    def flaky(path):
        calls.append(path)
        if len(calls) < 2:
            raise OSError("blip")
        return {"x": 1}

    assert sinks.safe_extract(flaky, "v.mp4", policy=pol) == "done"
    assert "DECODE LADDER" not in capsys.readouterr().out


# ----------------------------------------------------------- CLI summary

def test_cli_run_quarantines_and_tallies(tmp_path, capsys, monkeypatch):
    """End-to-end through cli.main: run 1 fails a corrupt video after
    retry_attempts tries and journals it; run 2 quarantines it via the
    journal (no re-decode); retry_failed=true re-runs it."""
    from video_features_tpu.cli import main
    monkeypatch.setenv("VFT_WEIGHTS_DIR", str(tmp_path / "w"))
    bad = tmp_path / "v_corrupt.mp4"
    bad.write_bytes(b"\x00\x01 junk that cv2 cannot open" * 64)
    argv = [
        "feature_type=resnet", "model_name=resnet18", "device=cpu",
        "batch_size=4", "allow_random_weights=true",
        "on_extraction=save_numpy", "retry_attempts=2",
        "retry_backoff_s=0", f"output_path={tmp_path / 'o'}",
        f"tmp_path={tmp_path / 't'}", f"video_paths={bad}",
    ]
    main(argv)
    out1 = capsys.readouterr().out
    assert "1 failed" in out1 and "POISON=1" in out1
    journal_path = tmp_path / "o" / "resnet" / "resnet18" / "_failures.jsonl"
    assert journal_path.exists()
    recs = [json.loads(l) for l in open(journal_path) if l.strip()]
    assert len(recs) == 1 and recs[0]["category"] == faults.POISON
    assert recs[0]["attempts"] == 2

    main(argv)
    out2 = capsys.readouterr().out
    assert "1 quarantined" in out2 and "0 failed" in out2
    # still exactly one record: quarantine skips never append
    assert len([l for l in open(journal_path) if l.strip()]) == 1

    main(argv + ["retry_failed=true"])
    out3 = capsys.readouterr().out
    assert "1 failed" in out3  # re-ran (and failed again: still corrupt)


# ------------------------------------------- the suite's own time limit

_TWO_TESTS = """
import ctypes
import time


def test_outlasts_the_limit():
    if {stuck}:
        # a main thread no signal handler can reach: the second lock of a
        # plain mutex never returns (cv2's read() under a released capture
        # stood still the same way)
        libc = ctypes.CDLL(None)
        mutex = ctypes.create_string_buffer(64)
        libc.pthread_mutex_lock(mutex)
        libc.pthread_mutex_lock(mutex)
    time.sleep(30)


def test_after_it():
    assert True
"""

# the limit is a constant of tests/conftest.py with no option to set it:
# the child's conftest takes that module's hooks and fixture and a limit of 1 s
_ONE_SECOND_CONFTEST = """
import importlib.util

spec = importlib.util.spec_from_file_location("repo_conftest", {conftest!r})
repo_conftest = importlib.util.module_from_spec(spec)
spec.loader.exec_module(repo_conftest)
repo_conftest.TEST_TIME_LIMIT_S = 1
pytest_configure = repo_conftest.pytest_configure
pytest_handlecrashitem = repo_conftest.pytest_handlecrashitem
_time_limit = repo_conftest._time_limit
"""

_AS_THE_DRIVER = ["-p", "xdist", "-n", "2", "--dist", "loadfile"]


@pytest.mark.parametrize("stuck,how", [
    (False, ["-p", "no:xdist"]),
    (False, _AS_THE_DRIVER),
    (True, _AS_THE_DRIVER),
], ids=["sleeping-serial", "sleeping-xdist", "stuck-in-c-xdist"])
def test_time_limit_fails_the_test_and_the_run_goes_on(tmp_path, stuck, how):
    """A test that outlasts the limit fails with every thread's stack on
    stderr, and the test after it still runs and passes: the signal's
    handler where the main thread can be interrupted, the end of the
    worker (reported and replaced by xdist) where it cannot."""
    if "xdist" in how:
        pytest.importorskip("xdist")
    (tmp_path / "conftest.py").write_text(_ONE_SECOND_CONFTEST.format(
        conftest=str(REPO / "tests" / "conftest.py")))
    (tmp_path / "test_two.py").write_text(_TWO_TESTS.format(stuck=stuck))
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         *how, "test_two.py"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=200)
    assert "1 failed, 1 passed" in done.stdout, (done.stdout[-3000:],
                                                 done.stderr[-3000:])
    assert "test_two.py::test_outlasts_the_limit" in done.stdout
    # the stacks: the test's own frame, on the real stderr
    assert "most recent call first" in done.stderr
    assert "in test_outlasts_the_limit" in done.stderr
    if not stuck:
        assert "TIME LIMIT: test_two.py::test_outlasts_the_limit" \
            in done.stderr
