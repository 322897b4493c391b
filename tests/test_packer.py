"""Cross-video clip batching (parallel/packer.py + clip_stack wiring).

The packer's contract: per-video results identical to the per-video-stream
path, any thread interleaving, no deadlock when every worker closes at
once with a part-filled group."""
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from video_features_tpu.parallel.packer import ClipPacker


class FakeRunner:
    """Row-wise 'device' forward: mean over all but the leading axis, with
    a jitter delay so drain/dispatch interleavings actually vary."""

    def __init__(self, delay: float = 0.0):
        self.delay = delay
        self.groups = []

    def dispatch(self, group: np.ndarray) -> np.ndarray:
        if self.delay:
            time.sleep(self.delay)
        self.groups.append(group.shape[0])
        return group.reshape(group.shape[0], -1).mean(axis=1, keepdims=True)


def _stack(video: int, idx: int) -> np.ndarray:
    # identifiable content: the fake forward recovers video*1000 + idx
    return np.full((4, 8, 8, 3), float(video * 1000 + idx), np.float32)


def test_single_video_ragged_flush():
    """One video, fewer clips than the batch: the all-closing flush rule
    must dispatch the ragged group instead of deadlocking."""
    runner = FakeRunner()
    p = ClipPacker(runner, batch=8)
    h = p.open_video()
    for i in range(3):
        p.add(h, _stack(0, i))
    rows = p.close_video(h)
    assert rows.shape == (3, 1)
    np.testing.assert_array_equal(rows[:, 0], [0.0, 1.0, 2.0])
    assert runner.groups == [3]  # one ragged dispatch, at close


def test_groups_fill_across_videos():
    """Sequential adds from two videos share one full-size group."""
    runner = FakeRunner()
    p = ClipPacker(runner, batch=4)
    h1, h2 = p.open_video(), p.open_video()
    p.add(h1, _stack(1, 0))
    p.add(h2, _stack(2, 0))
    p.add(h1, _stack(1, 1))
    p.add(h2, _stack(2, 1))  # fills -> dispatches a packed group
    assert runner.groups == [4]
    r1 = p.close_video(h1)
    np.testing.assert_array_equal(r1[:, 0], [1000.0, 1001.0])
    r2 = p.close_video(h2)
    np.testing.assert_array_equal(r2[:, 0], [2000.0, 2001.0])


def test_empty_video():
    p = ClipPacker(FakeRunner(), batch=4)
    h = p.open_video()
    assert p.close_video(h).shape == (0,)


def test_abort_unwedges_closers():
    """Per-video error isolation: a video that dies after open_video must
    not leave the open count elevated — otherwise the all-closing flush
    rule can never fire and every other worker's close_video hangs."""
    runner = FakeRunner()
    p = ClipPacker(runner, batch=8)
    healthy, doomed = p.open_video(), p.open_video()
    p.add(healthy, _stack(1, 0))
    p.add(doomed, _stack(2, 0))
    p.abort_video(doomed)  # what the extractor's except-path calls
    done = []

    def close_healthy():
        done.append(p.close_video(healthy))

    t = threading.Thread(target=close_healthy)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive(), "close_video wedged after a peer aborted"
    np.testing.assert_array_equal(done[0][:, 0], [1000.0])
    # the aborted video's buffered clip was discarded, not computed: the
    # ragged flush carried only the healthy video's single clip
    assert runner.groups == [1]


@pytest.mark.parametrize("batch,workers", [(4, 4), (8, 3)])
def test_concurrent_videos_exact_rows(batch, workers):
    """Many threads, ragged per-video clip counts (including zero), slow
    fake device: every video gets exactly its rows, in clip order."""
    runner = FakeRunner(delay=0.002)
    p = ClipPacker(runner, batch=batch, depth=2)
    rng = np.random.default_rng(0)
    counts = [int(c) for c in rng.integers(0, 6, size=10)]

    def run_video(vid: int) -> np.ndarray:
        h = p.open_video()
        for i in range(counts[vid]):
            p.add(h, _stack(vid, i))
            time.sleep(0.001 * (vid % 3))
        return p.close_video(h)

    with ThreadPoolExecutor(max_workers=workers) as pool:
        results = list(pool.map(run_video, range(len(counts))))
    for vid, rows in enumerate(results):
        assert rows.shape[0] == counts[vid], (vid, rows.shape)
        if counts[vid]:
            np.testing.assert_array_equal(
                rows[:, 0], [vid * 1000 + i for i in range(counts[vid])])
    # conservation: every clip dispatched exactly once
    assert sum(runner.groups) == sum(counts)


class _FailsOnArray:
    """Stand-in for a device buffer whose D2H read surfaces a runtime
    error (what a deferred JAX computation failure looks like at
    np.asarray time)."""

    def __array__(self, dtype=None, copy=None):
        raise RuntimeError("device exploded during D2H")


class PoisonRunner(FakeRunner):
    """FakeRunner whose Nth dispatched group fails lazily at
    materialization — the async-dispatch failure mode."""

    def __init__(self, fail_group: int):
        super().__init__()
        self.fail_group = fail_group

    def dispatch(self, group: np.ndarray) -> np.ndarray:
        gi = len(self.groups)
        out = super().dispatch(group)
        return _FailsOnArray() if gi == self.fail_group else out


def test_device_failure_poisons_only_group_members():
    """A group that dies on device must fail exactly its member videos'
    close_video (with the device error chained) while videos whose clips
    sit in healthy groups complete normally — no hang, no cross-talk."""
    runner = PoisonRunner(fail_group=1)
    p = ClipPacker(runner, batch=2)
    h1, h2, h3 = p.open_video(), p.open_video(), p.open_video()
    p.add(h1, _stack(1, 0))
    p.add(h1, _stack(1, 1))   # group 0 (healthy) dispatches
    p.add(h2, _stack(2, 0))
    p.add(h3, _stack(3, 0))   # group 1 (poisoned) dispatches
    rows = p.close_video(h1)
    np.testing.assert_array_equal(rows[:, 0], [1000.0, 1001.0])
    for doomed in (h2, h3):
        with pytest.raises(RuntimeError, match="failed on device"):
            p.close_video(doomed)


def test_dispatch_failure_propagates_and_poisons_peers():
    """runner.dispatch raising synchronously must surface at the add()
    that filled the group AND poison the group's other members so their
    close_video raises instead of spinning on clips that never ran."""

    class Boom(FakeRunner):
        def dispatch(self, group):
            raise RuntimeError("compile blew up")

    p = ClipPacker(Boom(), batch=2)
    h1, h2 = p.open_video(), p.open_video()
    p.add(h1, _stack(1, 0))
    with pytest.raises(RuntimeError, match="compile blew up"):
        p.add(h2, _stack(2, 0))  # fills the group -> dispatch fails
    p.abort_video(h2)  # what the adder's extractor except-path does
    with pytest.raises(RuntimeError, match="failed on device"):
        p.close_video(h1)


def test_stack_mismatch_poisons_members():
    """np.stack failing inside _dispatch (mismatched clip shapes) has
    already consumed the clips from the buffer, so it must poison the
    members like a device failure — not strand their pending counts."""
    p = ClipPacker(FakeRunner(), batch=2)
    h1, h2 = p.open_video(), p.open_video()
    p.add(h1, _stack(1, 0))
    with pytest.raises(ValueError):  # what np.stack raises for ragged shapes
        p.add(h2, np.zeros((2, 3, 3, 3), np.float32))
    p.abort_video(h2)
    with pytest.raises(RuntimeError, match="failed on device"):
        p.close_video(h1)


def test_add_fails_fast_after_poison():
    """Once a video's group has failed, further add() calls must raise
    immediately instead of decoding + dispatching doomed clips."""
    runner = PoisonRunner(fail_group=0)
    p = ClipPacker(runner, batch=2, depth=1)
    h1, h2 = p.open_video(), p.open_video()
    p.add(h1, _stack(1, 0))
    p.add(h2, _stack(2, 0))   # fills group 0 (poisoned lazily)
    p.add(h1, _stack(1, 1))
    p.add(h2, _stack(2, 1))   # fills group 1 -> inflight(2) > depth(1)
    # forces a drain, materializing poisoned group 0: errors recorded
    with pytest.raises(RuntimeError, match="failed on device"):
        p.add(h1, _stack(1, 2))


def _write_clip(path: str, frames: int, seed: int) -> str:
    cv2 = pytest.importorskip("cv2")
    w = cv2.VideoWriter(path, cv2.VideoWriter_fourcc(*"mp4v"),
                        16.0, (64, 48))
    if not w.isOpened():
        pytest.skip("cv2 cannot encode mp4v")
    yy, xx = np.mgrid[0:48, 0:64].astype(np.float32)
    for t in range(frames):
        frame = np.stack([
            127 + 120 * np.sin(xx / 9 + t / 5 + seed),
            127 + 120 * np.sin(yy / 7 - t / 6 + 2 * seed),
            127 + 120 * np.sin((xx + yy) / 11 + t / 4 + 3 * seed),
        ], axis=-1)
        w.write(frame.clip(0, 255).astype(np.uint8))
    w.release()
    return path


def test_cross_video_survives_corrupt_video(tmp_path):
    """Per-video error isolation under packing, end to end: one unreadable
    video among healthy ones must be reported failed while every healthy
    video still completes (the packer abort path; without it the run
    wedges in close_video)."""
    from video_features_tpu.cli import main

    vids = [_write_clip(str(tmp_path / f"v{i}.mp4"), 40, i) for i in range(2)]
    bad = tmp_path / "broken.mp4"
    bad.write_bytes(b"not a video at all")
    vids.insert(1, str(bad))

    main([
        "feature_type=r21d", "device=cpu", "allow_random_weights=true",
        "on_extraction=save_numpy", f"output_path={tmp_path / 'out'}",
        f"tmp_path={tmp_path / 'tmp'}", "clip_batch_size=8",
        "video_workers=2", "cross_video_batching=true",
        "video_paths=[" + ",".join(vids) + "]",
    ])
    done = sorted(p.name for p in (tmp_path / "out").rglob("*_r21d.npy"))
    assert done == ["v0_r21d.npy", "v1_r21d.npy"], done


@pytest.mark.slow  # ~54s E2E; the unit-level packer tests keep quick coverage
def test_r21d_cross_video_outputs_identical(tmp_path):
    """E2E through the real extractor: cross_video_batching=true over
    several short videos (each well under one clip_batch_size group) must
    write byte-identical features to the unpacked path, independent of
    worker interleaving."""
    from video_features_tpu.cli import main

    vids = [_write_clip(str(tmp_path / f"v{i}.mp4"), 40 + 16 * i, i)
            for i in range(3)]

    def run(out, packed, workers):
        main([
            "feature_type=r21d", "device=cpu", "allow_random_weights=true",
            "on_extraction=save_numpy", f"output_path={tmp_path / out}",
            f"tmp_path={tmp_path / ('tmp_' + out)}", "clip_batch_size=8",
            f"video_workers={workers}",
            f"cross_video_batching={'true' if packed else 'false'}",
            "video_paths=[" + ",".join(vids) + "]",
        ])
        return {
            p.name: np.load(p)
            for p in sorted((tmp_path / out).rglob("*_r21d.npy"))
        }

    plain = run("plain", packed=False, workers=1)
    packed = run("packed", packed=True, workers=2)
    assert set(plain) == set(packed) and len(plain) == 3
    for name in plain:
        assert plain[name].shape == packed[name].shape, name
        np.testing.assert_allclose(packed[name], plain[name],
                                   atol=1e-5, rtol=1e-5, err_msg=name)


# -- SegmentPacker: several documents' windows in one token row -----------------

class _LineRunner:
    """Stands for the token step: line ``s - 1`` of a row is (sum of the ids
    of segment ``s``, its token count), so a routed line says whose it is."""
    fixed_batch = 2

    def __init__(self, segments):
        self.segments = segments
        self.groups = []

    def dispatch(self, group):
        self.groups.append(np.array(group))
        ids, seg = group[:, 0], group[:, 1]
        lines = np.zeros((len(group), self.segments, 2), np.float32)
        for s in range(1, self.segments + 1):
            lines[:, s - 1, 0] = np.where(seg == s, ids, 0).sum(axis=1)
            lines[:, s - 1, 1] = (seg == s).sum(axis=1)
        return lines


def _segments(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 100, n).astype(np.int32) for n in lengths]


def test_segment_packer_fills_rows_and_routes_lines_back():
    from video_features_tpu.parallel.packer import SegmentPacker
    runner = _LineRunner(segments=4)
    packer = SegmentPacker(runner, batch=2, row_len=16, max_segments=4)
    docs = {"a": _segments(0, (5, 7)), "b": _segments(1, (6, 16, 3)),
            "c": _segments(2, (2,))}
    got = {}

    def worker(name):
        h = packer.open_video()
        for tokens in docs[name]:
            packer.add(h, tokens)
        got[name] = packer.close_video(h)

    threads = [threading.Thread(target=worker, args=(n,)) for n in docs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    for name, windows in docs.items():
        assert got[name].shape == (len(windows), 2)
        assert [tuple(line) for line in got[name]] == [
            (float(w.sum()), float(len(w))) for w in windows]
    for group in runner.groups:
        assert group.shape[1:] == (2, 16) and group.dtype == np.int32
        for ids, seg in group:
            # segments 1, 2, ... as contiguous runs, padding (0) behind them
            filled = int((seg > 0).sum())
            assert (seg[filled:] == 0).all() and (ids[filled:] == 0).all()
            assert (np.diff(seg[:filled]) >= 0).all() and seg.max() <= 4
    assert sum(int((g[:, 1] > 0).sum()) for g in runner.groups) == 39


def test_segment_packer_seals_a_row_at_its_bounds_and_forgets_an_abort():
    from video_features_tpu.parallel.packer import SegmentPacker
    runner = _LineRunner(segments=2)
    packer = SegmentPacker(runner, batch=1, row_len=8, max_segments=2)
    gone = packer.open_video()
    packer.add(gone, np.array([9, 9, 9], np.int32))
    packer.abort_video(gone)               # leaves the open row empty again
    h = packer.open_video()
    for tokens in ([1, 1], [2], [3, 3, 3], [4] * 6):
        packer.add(h, np.array(tokens, np.int32))
    lines = packer.close_video(h)
    assert [tuple(line) for line in lines] == [(2, 2), (2, 1), (9, 3),
                                               (24, 6)]
    # [1,1]+[2] (two segments: the bound), [3,3,3] (the next does not
    # fit), [4]*6: three rows, none holds the aborted document's tokens
    assert [int((g[0, 1] > 0).sum()) for g in runner.groups] == [3, 3, 6]
    with pytest.raises(ValueError, match="a segment of 9 tokens"):
        packer.add(packer.open_video(), np.arange(9, dtype=np.int32))


def test_a_flush_waits_for_the_group_that_is_being_copied_out():
    """A served loop: six workers, one short document each, again and again,
    on a device that takes 40 ms a dispatch. A group that some thread is
    copying out has left ``_inflight`` but not the device: a document that
    arrives meanwhile must wait for it and leave with the others that
    gathered, not alone behind it (1.07 documents a dispatch before this
    was counted; 1, 5, 1, 5, ... since)."""
    from video_features_tpu.parallel.packer import SegmentPacker

    class Late:
        def __init__(self, lines, ready):
            self.lines, self.ready = lines, ready

        def __array__(self, dtype=None, copy=None):
            time.sleep(max(0.0, self.ready - time.perf_counter()))
            return self.lines

    class SlowRunner:
        fixed_batch = 4

        def __init__(self):
            self.free, self.documents, self.lock = 0.0, [], threading.Lock()

        def dispatch(self, group):
            with self.lock:
                self.free = max(time.perf_counter(), self.free) + 0.04
                self.documents.append(int(group[:, 1].max(axis=1).sum()))
                return Late(np.zeros((len(group), 8, 1), np.float32),
                            self.free)

    runner = SlowRunner()
    packer = SegmentPacker(runner, batch=4, row_len=4096, max_segments=8)
    until = time.perf_counter() + 1.5

    def worker():
        while time.perf_counter() < until:
            h = packer.open_video()
            packer.add(h, np.ones(300, np.int32))
            assert packer.close_video(h).shape == (1, 1)
            time.sleep(0.002)   # the response, the next claim

    threads = [threading.Thread(target=worker) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    assert np.mean(runner.documents) >= 2.0, runner.documents
