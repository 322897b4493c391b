"""Clip-stack extraction pipeline (R(2+1)D, S3D).

Re-design of the reference's whole-video + per-slice serial loop
(reference models/r21d/extract_r21d.py:60-94, models/s3d/extract_s3d.py:40-75):

  host:   stream-decode -> per-frame resize/crop -> per-frame wire array
          (float32 (H, W, 3) by default; uint8, or packed-I420 uint8
          (H*W*3/2,), under the compressed ingest modes)
          -> `form_slices` windows (trailing partial stack dropped, same
          observable contract as reference utils/utils.py:59-68)
  device: (clip_batch, stack, *frame_wire_shape) fixed-shape jitted forward,
          the clip-batch axis sharded over the mesh's data axis.

Where the reference runs batch=1 slices sequentially (extract_r21d.py:84-88),
clips here are batched into one jitted call — each 3D-conv matmul gets a
bigger batch dim for the MXU and ragged tails are padded, so exactly one
executable per (stack_size, H, W) is compiled.
"""
from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional

import numpy as np

from ..config import Config
from ..parallel.mesh import DataParallelApply
from ..telemetry import trace
from ..utils.io import Prefetcher, VideoSource
from ..utils.lists import form_slices
from .base import BaseExtractor


class ClipStackExtractor(BaseExtractor):
    """Families plug in ``host_transform``, ``runner``, defaults, show_pred."""

    #: host->device wire formats a family supports. The pipeline is
    #: H2D-bandwidth-bound, so precision=bfloat16 defaults to uint8 (3 B/px;
    #: <=1/510 quantization noise, below bf16 input rounding) instead of
    #: float32 (12 B/px, the bit-exact golden default). Families may add
    #: opt-in 'yuv420' (packed I420, 1.5 B/px, colorspace on device — the
    #: maximum-throughput mode bench.py measures).
    supported_ingest = ("uint8", "float32")

    #: families whose host transform is entirely channel-independent
    #: (float conversion, resize, crop) set 'bgr' and reorder channels on
    #: their smallest intermediate instead — this skips a full-resolution
    #: cv2.cvtColor per decoded frame, bit-identically (utils/io.py
    #: _FrameStream).
    #:
    #: INVARIANT (a subclass that overrides either side must keep both in
    #: step): ``host_transform`` consumes frames in EXACTLY this channel
    #: order — declaring 'bgr' without the transform performing (or
    #: deferring) the RGB reorder silently channel-swaps every feature.
    #: tests/test_extractors_shared.py asserts the wiring equivalence for
    #: every registered family; the per-family torch-oracle E2E tests pin
    #: the actual values.
    frame_channel_order = "rgb"

    def __init__(self, args: Config, default_stack: int, default_step: int) -> None:
        super().__init__(args)
        self.model_name = args.get("model_name")
        self.stack_size = args.get("stack_size") or default_stack
        self.step_size = args.get("step_size") or default_step
        self.extraction_fps = args.get("extraction_fps")
        self.clip_batch_size = int(args.get("clip_batch_size") or 8)
        self.output_feat_keys = [self.feature_type]
        self.host_transform: Optional[Callable] = None
        self.runner: Optional[DataParallelApply] = None
        self.ingest = self._resolve_ingest(
            args, "uint8" if self.precision == "bfloat16" else "float32")
        # cross_video_batching=true: ONE clip buffer shared across the
        # video_workers threads, so device groups dispatch only when FULL
        # (parallel/packer.py) — lifts sustained throughput on short-video
        # corpora toward the fixed-shape bench steady state and makes big
        # clip_batch_size (128 is the v5e sweet spot) practical there.
        # Per-video outputs are identical to the unpacked path (row-wise
        # forward; asserted in tests/test_packer.py).
        self.cross_video = bool(args.get("cross_video_batching", False))
        if self.cross_video and self.show_pred:
            raise NotImplementedError(
                "cross_video_batching=true is incompatible with "
                "show_pred=true (predictions print per video group; packed "
                "groups interleave videos)")
        self._packer = None
        self._packer_lock = threading.Lock()

    def encode_wire(self, x01: np.ndarray) -> np.ndarray:
        """[0, 1] float HWC frame -> the configured wire format (the tail of
        every family's host transform)."""
        if self.ingest == "float32":
            return x01
        from ..ops import colorspace, preprocess as pp
        u8 = pp.quantize_u8(x01)
        if self.ingest == "uint8":
            return u8
        return colorspace.rgb_to_yuv420(u8)

    def _get_packer(self):
        from ..parallel.packer import ClipPacker
        with self._packer_lock:
            if self._packer is None:
                self._packer = ClipPacker(self.runner,
                                          batch=self.clip_batch_size)
            return self._packer

    def extract(self, video_path: str) -> Dict[str, np.ndarray]:
        src = self.video_source(video_path, batch_size=1,
                                fps=self.extraction_fps,
                          transform=self.host_transform,
                          channel_order=self.frame_channel_order)
        if self.cross_video:
            return self._extract_packed(src)
        return self._extract_grouped(src)

    def _iter_stacks(self, src: VideoSource):
        """Yield ((start, end), (stack, *frame_wire_shape)) clip windows
        under the form_slices drop-partial contract (reference
        utils/utils.py:59-68), one window at a time:

          - step >= stack (every family's default): disjoint windows are
            formed on the fly — frames between windows are dropped as
            decoded, and the Prefetcher's decode-ahead thread overlaps the
            consumer (bounded host memory; the reference reads the whole
            video up front and warns "could run out of memory here",
            extract_r21d.py:75-77);
          - step < stack: every frame participates in several windows, so
            the full frame sequence is materialized and windows are sliced
            from it (yielding per window keeps peak memory at sequence +
            one group, not sequence x stack/step)."""
        if self.step_size < self.stack_size:
            frames = [f for f, _, _ in src.frames()]
            if not frames:
                return
            all_frames = np.stack(frames)  # (T, *frame_wire_shape)
            for s, e in form_slices(len(frames), self.stack_size,
                                    self.step_size):
                yield (s, e), all_frames[s:e]
            return
        gap = self.step_size - self.stack_size
        current: List[np.ndarray] = []
        start_idx = 0
        until_next = 0  # frames to drop before the next window starts
        for f, _, idx in Prefetcher(src.frames()):
            if until_next > 0:
                until_next -= 1
                continue
            if not current:
                start_idx = idx
            current.append(f)
            if len(current) == self.stack_size:
                with trace.span("batch.assemble", frames=self.stack_size):
                    stack = np.stack(current)
                yield (start_idx, start_idx + self.stack_size), stack
                current.clear()
                until_next = gap
        # a trailing partial stack is dropped by falling off the loop

    def _extract_grouped(self, src: VideoSource) -> Dict[str, np.ndarray]:
        """Per-video async groups: windows batch into clip_batch_size
        groups dispatched through this video's own FeatureStream (submit
        returns immediately; only a depth-overflow pop or the final
        finish() blocks on D2H), so decode and device compute overlap. The
        trailing group goes out ragged (padded on dispatch)."""
        vid_feats: List[np.ndarray] = []
        stacks: List[np.ndarray] = []
        windows: List = []
        stream = self._make_stream()

        def flush():
            with trace.span("batch.assemble", rows=len(stacks)):
                group = np.stack(stacks)
            stream.submit(group, ctx=(list(windows), group))
            stacks.clear()
            windows.clear()

        for window, stack in self._iter_stacks(src):
            windows.append(window)
            stacks.append(stack)
            if len(stacks) == self.clip_batch_size:
                flush()
        if stacks:
            flush()
        done = stream.finish()
        with trace.span("batch.collect", batches=len(done)):
            for bi, feats in enumerate(done):
                if self.parity:
                    # backbone seam: per-group clip activations off the
                    # device
                    from ..telemetry import parity as _parity
                    _parity.tap("backbone", self.feature_type, feats,
                                video=str(src.path),
                                feature_type=self.feature_type, index=bi)
                vid_feats.extend(list(feats))
            return {self.feature_type: np.array(vid_feats)}

    def _extract_packed(self, src: VideoSource) -> Dict[str, np.ndarray]:
        """Cross-video group packing: clips go straight into the shared
        packer (one per extractor, fed by all video_workers threads) and
        come back per video in clip order; groups dispatch only when full
        (parallel/packer.py). The abort path keeps per-video error
        isolation from wedging other workers' close waits."""
        packer = self._get_packer()
        handle = packer.open_video()
        try:
            for _, stack in self._iter_stacks(src):
                packer.add(handle, stack)
        except BaseException:
            packer.abort_video(handle)
            raise
        feats = packer.close_video(handle)
        if self.parity:
            # backbone seam: the packer returns this video's clips in
            # order as one array — a single index-0 record per video
            from ..telemetry import parity as _parity
            _parity.tap("backbone", self.feature_type, feats,
                        video=str(src.path), feature_type=self.feature_type)
        return {self.feature_type: feats}

    def _make_stream(self):
        return self.feature_stream(
            self.runner,
            on_result=lambda feats, ctx: self.maybe_show_pred(feats, *ctx))

    def maybe_show_pred(self, feats: np.ndarray, slices,
                        group: Optional[np.ndarray] = None) -> None:
        pass
