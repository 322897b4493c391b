"""VideoSource: batching, overlap, fps resampling, timestamp contract."""
from pathlib import Path

import numpy as np
import pytest

from video_features_tpu.utils.io import (VideoSource, fps_filter_map,
                                         get_video_props, read_video_frames)
from video_features_tpu.utils.lists import form_slices

pytestmark = pytest.mark.quick


def test_video_props(sample_video):
    props = get_video_props(sample_video)
    assert props["num_frames"] == 355
    assert props["height"] == 240 and props["width"] == 320
    assert abs(props["fps"] - 19.62) < 0.01


def test_native_fps_iteration(sample_video):
    src = VideoSource(sample_video, batch_size=64)
    total, first_ts = 0, None
    for batch, times, indices in src:
        assert len(batch) == len(times) == len(indices)
        assert len(batch) <= 64
        if first_ts is None:
            first_ts = times[0]
            assert indices[0] == 0
        total += len(batch)
    assert first_ts == 0.0
    assert total == len(src) == 355


def test_timestamps_are_index_over_fps(sample_video):
    src = VideoSource(sample_video, batch_size=16)
    for batch, times, indices in src:
        for t, i in zip(times, indices):
            assert t == pytest.approx(i / src.fps * 1000.0)
        break


def test_overlap_carries_frames(sample_video):
    src = VideoSource(sample_video, batch_size=8, overlap=1)
    batches = list(src)
    # first batch: 8 new; later: 1 carried + 7 new
    assert batches[0][2][0] == 0
    for prev, cur in zip(batches, batches[1:]):
        assert cur[2][0] == prev[2][-1]  # first index of batch = last of prev
    # every frame consumed exactly once beyond the overlap duplicates
    all_idx = [i for _, _, idx in batches for i in idx]
    uniq = sorted(set(all_idx))
    assert uniq == list(range(355))


def test_fps_resampling_count_and_fps(sample_video):
    src = VideoSource(sample_video, batch_size=4, fps=1)
    assert src.fps == 1.0
    n = sum(len(b) for b, _, _ in src)
    # 355 frames @19.62fps = ~18.1s -> 18 or 19 one-fps frames
    assert n == len(src)
    assert 17 <= n <= 19


def test_total_resampling(sample_video):
    src = VideoSource(sample_video, batch_size=4, total=10)
    n = sum(len(b) for b, _, _ in src)
    assert n <= 10
    assert n >= 9


def test_fps_and_total_exclusive(sample_video):
    with pytest.raises(ValueError):
        VideoSource(sample_video, fps=5, total=10)


def test_fps_filter_map_properties():
    # downsample 100 frames 30->10 fps: every 3rd frame (the last of the
    # input frames rounding into each output slot wins, as in ffmpeg's
    # fps filter), monotonic
    m = fps_filter_map(100, 30.0, 10.0)
    assert np.array_equal(m[:-1], 3 * np.arange(len(m) - 1) + 1)
    # the stream ends at EOF pts (num_frames/src_fps): exactly
    # round(100 * 10/30) = 33 output frames, and trailing inputs whose slot
    # lands past that cutoff are dropped (golden-pinned in test_golden.py:
    # the real binary emits 54 frames at fps=3, not 55)
    assert len(m) == 33
    assert m[-1] == 97
    assert np.all(np.diff(m) >= 0)
    # upsample duplicates frames up to the EOF cutoff: round(10 * 2) = 20
    m2 = fps_filter_map(10, 10.0, 20.0)
    assert len(m2) == 20
    assert np.all(np.diff(m2) <= 1)
    # identity
    m3 = fps_filter_map(50, 25.0, 25.0)
    assert np.array_equal(m3, np.arange(50))
    # exact 2x downsample must be temporally uniform (half-away-from-zero
    # rounding; banker's rounding would give jittery [1,2,5,6,9,...])
    m4 = fps_filter_map(20, 30.0, 15.0)
    assert np.array_equal(m4[:-1], 2 * np.arange(len(m4) - 1))


def test_read_video_frames_shape(sample_video):
    frames, fps = read_video_frames(sample_video)
    assert frames.shape == (355, 240, 320, 3)
    assert frames.dtype == np.uint8
    assert abs(fps - 19.62) < 0.01


def test_transform_applied(sample_video):
    src = VideoSource(sample_video, batch_size=2,
                      transform=lambda x: x[:10, :12].astype(np.float32))
    batch, _, _ = next(iter(src))
    assert batch[0].shape == (10, 12, 3)
    assert batch[0].dtype == np.float32
    # the frames() view (used by clip-stack extractors) must apply the
    # transform too — regression for the silently-skipped-resize bug
    frame, _, _ = next(iter(src.frames()))
    assert frame.shape == (10, 12, 3)
    assert frame.dtype == np.float32


def test_form_slices_drops_partial_tail():
    # reference utils/utils.py:59-68 contract
    assert form_slices(100, 15, 15) == [(0, 15), (15, 30), (30, 45), (45, 60),
                                        (60, 75), (75, 90)]
    assert form_slices(10, 4, 2) == [(0, 4), (2, 6), (4, 8), (6, 10)]
    assert form_slices(3, 4, 2) == []


def test_device_resize_matches_pil(rng):
    """ops/preprocess.py device_resize: the PIL-coefficient matmul resize
    must stay within 2 LSB of Pillow for both filters, up- and downscale."""
    from video_features_tpu.ops.preprocess import (device_resize,
                                                   pil_resize,
                                                   pil_resize_matrix)
    for (ih, iw, oh, ow) in ((240, 320, 256, 341), (240, 320, 112, 149),
                             (120, 90, 224, 168)):
        img = rng.integers(0, 255, size=(ih, iw, 3), dtype=np.uint8)
        for interp in ("bilinear", "bicubic"):
            ref = pil_resize(img, (oh, ow), interpolation=interp)
            rmat = pil_resize_matrix(ih, oh, interp)
            cmat = pil_resize_matrix(iw, ow, interp)
            got = np.asarray(device_resize(img[None], rmat, cmat))[0]
            d = np.abs(got - ref.astype(np.float64)).max()
            assert d <= 2.0, (interp, (ih, iw, oh, ow), d)


def test_frame_wise_device_resize_matches_host(sample_video, tmp_path,
                                               monkeypatch):
    """resize=device end to end (resnet): features must match the host-PIL
    path within the 2-LSB input quantization difference."""
    from video_features_tpu.config import load_config, parse_dotlist, \
        sanity_check
    from video_features_tpu.registry import get_extractor_cls

    monkeypatch.setenv("VFT_WEIGHTS_DIR", str(tmp_path / "weights"))

    def feats(resize):
        args = load_config("resnet", parse_dotlist([
            "feature_type=resnet", "model_name=resnet18", "device=cpu",
            "batch_size=8", "extraction_fps=2", "allow_random_weights=true",
            f"resize={resize}", f"output_path={tmp_path / 'o'}",
            f"tmp_path={tmp_path / 't'}", f"video_paths={sample_video}"]))
        sanity_check(args)
        return get_extractor_cls("resnet")(args).extract(sample_video)

    host = feats("host")
    dev = feats("device")
    np.testing.assert_array_equal(host["timestamps_ms"],
                                  dev["timestamps_ms"])
    a, b = host["resnet"], dev["resnet"]
    assert a.shape == b.shape
    cos = np.sum(a * b, axis=1) / (np.linalg.norm(a, axis=1)
                                   * np.linalg.norm(b, axis=1) + 1e-9)
    assert np.all(cos > 0.999), cos.min()


def test_device_resize_mixed_resolutions(sample_video, tmp_path, monkeypatch):
    """resize=device across videos of different source resolutions: the
    per-resolution runner cache must produce correct shapes for each (and
    features for the re-encoded small video must match its own host-path
    run)."""
    import cv2
    from video_features_tpu.config import load_config, parse_dotlist, \
        sanity_check
    from video_features_tpu.registry import get_extractor_cls

    monkeypatch.setenv("VFT_WEIGHTS_DIR", str(tmp_path / "weights"))
    # a second video at half resolution, synthesized from the sample
    small = str(tmp_path / "v_small.mp4")
    cap = cv2.VideoCapture(sample_video)
    w = cv2.VideoWriter(small, cv2.VideoWriter_fourcc(*"mp4v"), 20,
                        (160, 120))
    for _ in range(40):
        ok, frame = cap.read()
        if not ok:
            break
        w.write(cv2.resize(frame, (160, 120)))
    w.release()
    cap.release()

    def extractor(resize):
        args = load_config("resnet", parse_dotlist([
            "feature_type=resnet", "model_name=resnet18", "device=cpu",
            "batch_size=8", "extraction_fps=2", "allow_random_weights=true",
            f"resize={resize}", f"output_path={tmp_path / 'o'}",
            f"tmp_path={tmp_path / 't'}",
            f"video_paths=[{sample_video},{small}]"]))
        sanity_check(args)
        return get_extractor_cls("resnet")(args)

    ex = extractor("device")
    big = ex.extract(sample_video)["resnet"]
    sm = ex.extract(small)["resnet"]
    assert big.shape[1] == sm.shape[1] == 512 and len(sm) > 0
    assert len(ex._resize_runners) == 2  # one per source resolution
    # the small video agrees with its own host-path extraction
    sm_host = extractor("host").extract(small)["resnet"]
    cos = np.sum(sm * sm_host, axis=1) / (
        np.linalg.norm(sm, axis=1) * np.linalg.norm(sm_host, axis=1) + 1e-9)
    assert np.all(cos > 0.999), cos.min()


def test_channel_order_bgr_is_flipped_rgb(sample_video):
    """channel_order='bgr' must yield exactly the decoder frames the default
    mode yields, minus the cvtColor — i.e. the same bytes channel-reversed.
    (The deferred-reorder transforms in r21d/s3d/frame-wise device-resize
    rely on this identity.)"""
    rgb_src = VideoSource(sample_video, batch_size=3)
    bgr_src = VideoSource(sample_video, batch_size=3, channel_order="bgr")
    (rgb, _, _) = next(iter(rgb_src))
    (bgr, _, _) = next(iter(bgr_src))
    assert len(rgb) == len(bgr) == 3
    for r, b in zip(rgb, bgr):
        np.testing.assert_array_equal(r, b[:, :, ::-1])


def test_grab_skip_resampling_identical(sample_video):
    """The fps-filter catch-up loop grab()-skips dropped frames (no
    YUV->BGR conversion/copy for the ~95% discarded at low extraction
    fps). Frame SELECTION and bytes must be identical to full decode:
    compare against an index_map-driven full-decode reference."""
    src = VideoSource(sample_video, fps=2.0)
    picked = [(idx, f) for f, _, idx in src.frames()]
    # reference: decode everything, select by the same fps_filter_map
    full = [f for f, _, _ in VideoSource(sample_video).frames()]
    from video_features_tpu.utils.io import fps_filter_map, get_video_props
    props = get_video_props(sample_video)
    mapping = fps_filter_map(props["num_frames"], props["fps"], 2.0)
    assert [i for i, _ in picked] == list(range(len(mapping)))
    assert len(picked) == len(mapping)
    for (out_idx, frame), src_idx in zip(picked, mapping):
        np.testing.assert_array_equal(frame, full[src_idx])


def test_process_video_source_matches_inline(sample_video):
    """video_decode=process: the spawned-worker source yields exactly the
    inline source's frames/timestamps/indices and props, transform applied
    child-side (picklable callables, ops/host_transforms.py)."""
    from video_features_tpu.ops.host_transforms import MinSideResize
    from video_features_tpu.utils.io import ProcessVideoSource
    tf = MinSideResize(128)
    inline = VideoSource(sample_video, fps=2.0, transform=tf)
    proc = ProcessVideoSource(sample_video, fps=2.0, transform=tf)
    assert proc.fps == inline.fps
    assert proc.num_frames == inline.num_frames
    assert (proc.height, proc.width) == (inline.height, inline.width)
    got = list(proc.frames())
    want = list(inline.frames())
    assert len(got) == len(want) > 0
    for (gf, gt, gi), (wf, wt, wi) in zip(got, want):
        assert (gt, gi) == (wt, wi)
        np.testing.assert_array_equal(gf, wf)


def test_process_video_source_error_propagates(tmp_path):
    """A corrupt video fails the PARENT with a per-video error (the chaos
    contract), not a hung queue."""
    import pytest as _pytest
    from video_features_tpu.utils.io import ProcessVideoSource
    bad = tmp_path / "bad.mp4"
    bad.write_bytes(b"not a video" * 100)
    with _pytest.raises(RuntimeError, match="decode worker failed"):
        ProcessVideoSource(str(bad), fps=2.0)


def test_process_video_source_killed_worker_raises(sample_video):
    """A worker killed without running its except handler (OOM SIGKILL)
    must fail the video, not hang the parent on an untimed queue get
    (advisor r4). The timed get + liveness check turns it into the same
    per-video RuntimeError as a decode failure."""
    import os
    import signal
    import pytest as _pytest
    from video_features_tpu.utils.io import ProcessVideoSource
    src = ProcessVideoSource(sample_video, fps=2.0, depth=2)
    it = src.frames()
    next(it)  # worker is up and decoding
    os.kill(src._proc.pid, signal.SIGKILL)
    with _pytest.raises(RuntimeError, match="died without a result"):
        for _ in it:  # drain whatever was queued, then hit the dead worker
            pass


# ------------------------------------------------------- fps_mode=reencode


def test_reencode_mode_same_frame_timing(sample_video, tmp_path):
    """reencode (cv2 backend here; ffmpeg absent) must deliver the same
    frame COUNT and timestamps as select-mode — only pixel provenance
    differs (lossy codec). The timing rule is fps_filter_map on both
    paths."""
    from video_features_tpu.utils.io import VideoSource
    sel = VideoSource(sample_video, batch_size=4, fps=2.0)
    ren = VideoSource(sample_video, batch_size=4, fps=2.0,
                      fps_mode="reencode", tmp_path=str(tmp_path))
    sel_items = [(ts, idx) for _, ts, idx in sel.frames()]
    ren_items = [(ts, idx) for _, ts, idx in ren.frames()]
    assert len(sel_items) == len(ren_items) == sel.num_frames
    np.testing.assert_allclose([t for t, _ in sel_items],
                               [t for t, _ in ren_items], rtol=1e-9)
    assert ren.fps == pytest.approx(2.0)


def test_reencode_pixels_are_lossy_but_close(sample_video, tmp_path):
    """The re-encoded stream's pixels must be (a) different from the
    bit-exact select path (it IS a lossy generation) and (b) close to it
    (same underlying frames). Guards against off-by-one frame selection
    masquerading as codec noise."""
    from video_features_tpu.utils.io import VideoSource
    sel = [f for f, _, _ in VideoSource(sample_video, fps=2.0).frames()]
    ren = [f for f, _, _ in VideoSource(
        sample_video, fps=2.0, fps_mode="reencode",
        tmp_path=str(tmp_path)).frames()]
    assert len(sel) == len(ren)
    deltas = [np.abs(a.astype(np.int16) - b.astype(np.int16)).mean()
              for a, b in zip(sel, ren)]
    assert max(deltas) > 0, "reencode delivered bit-identical pixels — " \
        "the lossy intermediate is not actually being decoded"
    # a mis-selected frame pair in this synthetic/real clip differs by
    # far more than codec quantization noise
    assert np.mean(deltas) < 20.0, (
        f"mean |delta| {np.mean(deltas):.1f} u8-steps: frame selection "
        "diverged between the two modes, not just codec noise")


def test_reencode_tmp_file_cleanup(sample_video, tmp_path):
    from video_features_tpu.utils.io import VideoSource
    src = VideoSource(sample_video, fps=2.0, fps_mode="reencode",
                      tmp_path=str(tmp_path))
    tmp_file = Path(src._tmp_file)
    assert tmp_file.exists()
    for _ in src.frames():
        pass
    assert not tmp_file.exists(), "temp file must be removed after decode"
    keep = VideoSource(sample_video, fps=2.0, fps_mode="reencode",
                       tmp_path=str(tmp_path), keep_tmp=True)
    kept = Path(keep._tmp_file)
    for _ in keep.frames():
        pass
    assert kept.exists(), "keep_tmp=True must preserve the temp file"


def test_release_of_an_unread_source_drops_its_tmp_file(sample_video,
                                                        tmp_path):
    """release() touches no capture (nobody iterates, none is open) and
    still cleans up what the constructor made."""
    src = VideoSource(sample_video, fps=2.0, fps_mode="reencode",
                      tmp_path=str(tmp_path))
    tmp_file = Path(src._tmp_file)
    assert tmp_file.exists()
    src.release()
    assert not tmp_file.exists()
    with pytest.raises(RuntimeError, match="single-pass"):
        next(src.frames())


def test_cancelled_stream_is_released_by_the_thread_that_reads_it(
        sample_video, monkeypatch):
    """One thread owns a capture: cancel() from another thread only sets
    the flag, and the capture is released where it was opened."""
    import threading

    import cv2

    from video_features_tpu.utils.faults import DeadlineExceeded
    log = []  # (opened by, released by) per capture

    real = cv2.VideoCapture

    class RecordingCapture:
        def __init__(self, *a):
            self.cap = real(*a)
            self.opened_by = threading.get_ident()

        def release(self):
            log.append((self.opened_by, threading.get_ident()))
            self.cap.release()

        def __getattr__(self, name):
            return getattr(self.cap, name)

    monkeypatch.setattr(cv2, "VideoCapture", RecordingCapture)
    src = VideoSource(sample_video)
    first_frame, cancelled = threading.Event(), threading.Event()
    raised = []

    def iterate():
        try:
            for _ in src.frames():
                first_frame.set()
                cancelled.wait(5)
        except DeadlineExceeded as e:
            raised.append(e)

    reader = threading.Thread(target=iterate)
    reader.start()
    assert first_frame.wait(5)
    before = len(log)
    src.cancel("enough")
    assert len(log) == before, "cancel() released a capture"
    cancelled.set()
    reader.join(5)
    assert not reader.is_alive() and len(raised) == 1
    assert "enough" in str(raised[0])
    assert log[before:] == [(reader.ident, reader.ident)]
    assert all(o == r for o, r in log), log


@pytest.mark.parametrize("fps", [None, 1.0], ids=["every-frame", "fps-filter"])
def test_cancel_is_seen_within_one_source_frame(sample_video, monkeypatch,
                                                fps):
    """The flag is looked at before every source frame, the ones an fps
    filter drops included (~19 in a row at 1 fps): no decode call follows
    a cancel()."""
    from video_features_tpu.utils import io as vio
    from video_features_tpu.utils.faults import DeadlineExceeded
    src = VideoSource(sample_video, fps=fps)
    calls = []

    def counting(name):
        real = getattr(vio._FrameStream, name)

        def call(stream):
            calls.append(name)
            if len(calls) == 30:
                src.cancel("at the 30th source frame")
            return real(stream)
        return call

    monkeypatch.setattr(vio._FrameStream, "read", counting("read"))
    monkeypatch.setattr(vio._FrameStream, "skip", counting("skip"))
    with pytest.raises(DeadlineExceeded, match="30th source frame"):
        for _ in src.frames():
            pass
    assert len(calls) == 30
    assert ("skip" in calls) == (fps is not None)


def test_reencode_total_mode(sample_video, tmp_path):
    """total + reencode: the reference derives fps from total and decodes
    the re-encoded file capped at total frames (utils/io.py:83-89)."""
    from video_features_tpu.utils.io import VideoSource
    src = VideoSource(sample_video, total=9, fps_mode="reencode",
                      tmp_path=str(tmp_path))
    frames = list(src.frames())
    assert len(frames) <= 9
    assert len(frames) >= 8  # round(n*r) may fall one short of total


def test_reencode_second_pass_raises(sample_video, tmp_path):
    """cv2 fails silently on a missing path; a consumed single-pass
    reencode source must raise, not yield an empty stream."""
    from video_features_tpu.utils.io import VideoSource
    src = VideoSource(sample_video, fps=2.0, fps_mode="reencode",
                      tmp_path=str(tmp_path))
    for _ in src.frames():
        pass
    with pytest.raises(RuntimeError, match="single-pass"):
        next(src.frames())


# --------------------------------------------------- intra-video parallel


@pytest.mark.parametrize("workers,fps,overlap", [(2, 2.0, 0), (4, None, 0),
                                                 (3, 3.0, 1)])
def test_parallel_decode_bit_equal_to_serial(sample_video, workers, fps,
                                             overlap):
    """N seek-aligned segment decoders must reproduce the serial stream
    BIT-exactly — frames, timestamps, indices, batching, overlap."""
    from video_features_tpu.ops.host_transforms import ResizeCropTransform
    from video_features_tpu.utils.io import ParallelVideoSource, VideoSource
    kw = dict(batch_size=7, fps=fps, overlap=overlap,
              transform=ResizeCropTransform(80, 64, "bilinear", "uint8"))
    serial = list(VideoSource(sample_video, **kw))
    par = list(ParallelVideoSource(sample_video, decode_workers=workers,
                                   **kw))
    assert len(serial) == len(par)
    for (b1, t1, i1), (b2, t2, i2) in zip(serial, par):
        assert t1 == t2 and i1 == i2
        for f1, f2 in zip(b1, b2):
            np.testing.assert_array_equal(f1, f2)


def test_parallel_decode_corrupt_video_raises(tmp_path):
    from video_features_tpu.utils.io import ParallelVideoSource
    bad = tmp_path / "bad.mp4"
    bad.write_bytes(b"junk" * 200)
    with pytest.raises(ValueError):
        ParallelVideoSource(str(bad), fps=2.0, decode_workers=2)


def test_parallel_decode_rejects_reencode(sample_video, tmp_path):
    from video_features_tpu.utils.io import ParallelVideoSource
    with pytest.raises(NotImplementedError, match="fps_mode=select"):
        ParallelVideoSource(sample_video, fps=2.0, decode_workers=2,
                            fps_mode="reencode", tmp_path=str(tmp_path))


def test_parallel_decode_through_extractor(sample_video, tmp_path,
                                           monkeypatch):
    """video_decode=parallel end to end (resnet): features identical to
    the inline decode path — the factory wiring in extractors/base.py."""
    from video_features_tpu.config import load_config, parse_dotlist, \
        sanity_check
    from video_features_tpu.registry import get_extractor_cls
    monkeypatch.setenv("VFT_WEIGHTS_DIR", str(tmp_path / "w"))

    def feats(decode, extra=()):
        args = load_config("resnet", parse_dotlist([
            "feature_type=resnet", "model_name=resnet18", "device=cpu",
            "batch_size=8", "extraction_fps=2", "allow_random_weights=true",
            f"video_decode={decode}", f"output_path={tmp_path / 'o'}",
            f"tmp_path={tmp_path / 't'}",
            f"video_paths={sample_video}", *extra]))
        sanity_check(args)
        return get_extractor_cls("resnet")(args).extract(sample_video)

    inline = feats("inline")
    par = feats("parallel", ("decode_workers=3",))
    np.testing.assert_array_equal(inline["timestamps_ms"],
                                  par["timestamps_ms"])
    np.testing.assert_array_equal(inline["resnet"], par["resnet"])


def test_parallel_decode_lying_metadata_falls_back_to_recount(
        sample_video, monkeypatch, capsys):
    """ADVICE medium: a container whose metadata reports num_frames<=0 in
    native-fps mode must fall back to count_frames_by_decode (like the
    serial resample path) instead of spawning zero workers and silently
    yielding an empty stream."""
    from video_features_tpu.utils import io as io_mod
    real_props = io_mod.get_video_props

    def lying_props(path):
        props = real_props(path)
        props["num_frames"] = 0  # metadata lied; fps stays valid
        return props

    monkeypatch.setattr(io_mod, "get_video_props", lying_props)
    src = io_mod.ParallelVideoSource(sample_video, decode_workers=2,
                                     batch_size=64)
    assert len(src) == 355
    total = sum(len(b) for b, _, _ in src)
    assert total == 355
    assert "counted 355 by decode" in capsys.readouterr().out


def test_parallel_decode_lying_metadata_empty_stream_raises(
        tmp_path, monkeypatch):
    """Same fallback, but a stream with zero decodable frames must fail
    loudly, not emit an empty feature."""
    from video_features_tpu.utils import io as io_mod
    bad = tmp_path / "empty.mp4"
    bad.write_bytes(b"\x00" * 2048)
    monkeypatch.setattr(
        io_mod, "get_video_props",
        lambda path: dict(fps=19.62, num_frames=0, height=240, width=320))
    with pytest.raises(ValueError, match="No decodable frames"):
        io_mod.ParallelVideoSource(str(bad), decode_workers=2)


def test_segment_worker_seek_mismatch_degrades_to_serial(
        sample_video, monkeypatch, capsys):
    """ADVICE low: when CAP_PROP_POS_FRAMES does not land where asked
    (VFR/odd codecs), the segment worker must re-decode serially from
    frame 0 — same bytes, seek benefit lost — instead of silently
    emitting wrong frames."""
    import cv2
    from video_features_tpu.utils import io as io_mod
    real_capture = cv2.VideoCapture

    class _NoSeekCap:
        """Delegates everything but silently ignores frame seeks."""

        def __init__(self, path):
            self._cap = real_capture(path)

        def set(self, prop, val):
            if prop == cv2.CAP_PROP_POS_FRAMES:
                return True  # claims success, does nothing (VFR-style)
            return self._cap.set(prop, val)

        def __getattr__(self, name):
            return getattr(self._cap, name)

    class _ListQ:
        def __init__(self):
            self.items = []

        def put(self, item):
            self.items.append(item)

    # serial reference frames for source indices 100..119 (native fps)
    want = {}
    for f, _, i in io_mod.VideoSource(sample_video).frames():
        if 100 <= i < 120:
            want[i] = f

    monkeypatch.setattr(io_mod.cv2, "VideoCapture", _NoSeekCap)
    q = _ListQ()
    seg = dict(src_indices=np.arange(100, 120, dtype=np.int64),
               out_start=100, fps=19.62, transform=None,
               channel_order="rgb")
    io_mod._segment_decode_worker(q, sample_video, seg)

    assert "seek verification failed" in capsys.readouterr().out
    frames = [p for tag, p in q.items if tag == "frame"]
    assert q.items[-1] == ("done", 20)
    assert [idx for _, _, idx in frames] == list(range(100, 120))
    for x, _, idx in frames:
        np.testing.assert_array_equal(x, want[idx])
