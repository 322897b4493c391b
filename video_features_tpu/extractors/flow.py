"""Pair-wise optical-flow extraction pipeline (RAFT, PWC).

Re-design of reference models/_base/base_flow_extractor.py:17-154:

  host:   streaming decode of ``batch_size + 1`` frames with 1-frame overlap
          between batches (N+1 frames -> N flows; reference
          base_flow_extractor.py:77-85), optional PIL edge resize, uint8
  device: fixed-shape (B, 2, H, W, 3) uint8 pair batch -> replicate-pad to
          the model's stride multiple -> flow net -> unpad -> (B, H, W, 2)

The reference ships frames to the GPU as float32 and pads with a host-side
InputPadder; here the 4x-smaller uint8 batch is shipped and both the
[0,255] cast and the replicate padding run inside the jitted function (pad
amounts are static under jit). Timestamps: the duplicate overlap timestamp
between consecutive batches is dropped (base_flow_extractor.py:94-95).

Feature layout parity: the reference stores flows channel-first
``(N, 2, H, W)`` (``model(...)`` output `.tolist()`ed); we transpose our
NHWC device output on the host to keep saved arrays byte-compatible.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional

import numpy as np

from ..config import Config
from ..parallel.mesh import DataParallelApply
from ..telemetry import trace
from ..utils.io import Prefetcher, VideoSource
from ..utils import flow_viz
from .base import BaseExtractor


class OpticalFlowExtractor(BaseExtractor):
    """Families plug in ``runner`` ((B,2,H,W,3) uint8 -> (B,H,W,2) float)."""

    def __init__(self, args: Config) -> None:
        super().__init__(args)
        self.batch_size = int(args.get("batch_size") or 1)
        self.side_size = args.get("side_size")
        self.resize_to_smaller_edge = bool(args.get("resize_to_smaller_edge",
                                                    True))
        self.extraction_fps = args.get("extraction_fps")
        self.extraction_total = args.get("extraction_total")
        self.output_feat_keys = [self.feature_type, "fps", "timestamps_ms"]
        self.runner: Optional[DataParallelApply] = None
        #: set by subclasses for resize=device: the family forward taking
        #: uint8 pairs at the (resized) working geometry, and a builder
        #: producing a runner around a wrapped fwd with shared committed
        #: params (same pattern as frame_wise.py)
        self.base_fwd: Optional[Callable] = None
        self.runner_builder: Optional[Callable] = None

        #: resize=device (only meaningful with side_size): the per-frame PIL
        #: edge resize moves onto the MXU in front of the flow net; the host
        #: ships raw decoded frames. At small side_size the flow nets outrun
        #: a CPU core's PIL filtering, so this keeps the chip fed. Without
        #: side_size there is no resize in the pipeline at all, so the
        #: 'auto' default resolves to host.
        self.resize_mode = self._resolve_resize_mode(
            args, device_capable=self.side_size is not None)
        if self.side_size is None:
            self.resize_mode = "host"  # explicit resize=device: no-op too
        if self.resize_mode == "device" and self.show_pred:
            # show_pred overlays flow on the (resized) RGB frames, which the
            # host no longer has under device resize
            print("WARNING: resize=device is unsupported with show_pred; "
                  "using resize=host")
            self.resize_mode = "host"

        if self.side_size is not None and self.resize_mode == "host":
            from ..ops import preprocess as pp
            side = int(self.side_size)
            smaller = self.resize_to_smaller_edge

            def transform(rgb: np.ndarray) -> np.ndarray:
                return pp.pil_resize(rgb, side, to_smaller_edge=smaller)

            self.host_transform: Optional[Callable] = transform
        else:
            self.host_transform = None

    def _init_flow_runner(self, fwd, params, mesh) -> None:
        """Family-shared runner construction: the base runner plus the
        committed-param builder the device-resize cache wraps."""
        self.base_fwd = fwd
        self.runner = DataParallelApply(fwd, params, mesh=mesh,
                                        fixed_batch=self.batch_size)
        committed = self.runner.params  # one HBM copy across resolutions
        self.runner_builder = lambda f: DataParallelApply(
            f, committed, mesh=mesh, fixed_batch=self.batch_size)

    def _device_resize_runner(self, in_h: int, in_w: int) -> DataParallelApply:
        """Per-source-resolution runner: edge resize fused in front of the
        flow forward; committed params shared (one HBM copy)."""
        def build():
            from ..ops import preprocess as pp
            ow, oh = pp.resize_edge_size(in_w, in_h, int(self.side_size),
                                         self.resize_to_smaller_edge)
            resize = pp.make_device_resizer(in_h, in_w, oh, ow)
            base = self.base_fwd

            def fwd(params, raw_pairs_u8):  # (B, 2, in_h, in_w, 3)
                return base(params, resize(raw_pairs_u8))

            return self.runner_builder(fwd)

        return self._cached_resize_runner((in_h, in_w), build)

    def extract(self, video_path: str) -> Dict[str, np.ndarray]:
        video = self.video_source(
            video_path,
            batch_size=self.batch_size + 1,  # N+1 frames -> N flows
            fps=self.extraction_fps,
            total=self.extraction_total,
            transform=self.host_transform,
            overlap=1,
        )
        vid_feats: List[np.ndarray] = []
        timestamps_ms: List[float] = []
        first = True
        stream = None
        # decode-ahead: the next batch decodes while this one is on-device
        for batch, ts, _ in Prefetcher(video):
            if len(batch) < 2:
                # a single-frame video (or trailing lone frame in the first
                # batch) yields no pairs
                timestamps_ms.extend(ts if first else ts[1:])
                first = False
                continue
            if stream is None:
                # resize=device keys the fused-resize runner off the first
                # decoded frame's shape; async dispatch with a shallow
                # window: each pending output is a full (B, H, W, 2) float
                # field, so at most 2 wait on-device at once
                runner = (self._device_resize_runner(*batch[0].shape[:2])
                          if self.resize_mode == "device" else self.runner)
                stream = self.feature_stream(
                    runner, depth=2,
                    on_result=lambda flows, a: self.maybe_show_pred(flows, a))
            with trace.span("batch.assemble", rows=len(batch) - 1):
                arr = np.stack(batch)  # (n, H, W, 3) uint8
                pairs = np.stack([arr[:-1], arr[1:]], axis=1)
            stream.submit(pairs, ctx=arr)
            timestamps_ms.extend(ts if first else ts[1:])
            first = False
        done = stream.finish() if stream is not None else []
        # after the D2H: the channel-first transpose and the per-video
        # concatenation (72-323 MB a 10 s video at 240x320)
        with trace.span("batch.collect", batches=len(done)):
            for bi, flows in enumerate(done):
                # (n-1, H, W, 2) float32 per batch
                if self.parity:
                    # backbone seam: the raw per-batch flow field off the
                    # device, before the (0,3,1,2) sink transpose
                    from ..telemetry import parity as _parity
                    _parity.tap("backbone", self.feature_type, flows,
                                video=str(video_path),
                                feature_type=self.feature_type, index=bi)
                vid_feats.extend(list(flows.transpose(0, 3, 1, 2)))
            feats = np.array(vid_feats)
        return {
            self.feature_type: feats,
            "fps": np.array(video.fps),
            "timestamps_ms": np.array(timestamps_ms),
        }

    def maybe_show_pred(self, flows: np.ndarray, rgb_batch: np.ndarray) -> None:
        """Reference base_flow_extractor.py:139-154: show each flow frame
        under its first RGB frame in a cv2 window; headless fallback writes
        PNGs into tmp_path."""
        if not self.show_pred:
            return
        import cv2
        from pathlib import Path
        for i, flow in enumerate(flows):  # flows: (n, H, W, 2) NHWC
            img = rgb_batch[i].astype(np.float32)
            vis = flow_viz.flow_to_image(flow)
            stacked = np.concatenate([img, vis.astype(np.float32)], axis=0)
            bgr = stacked[:, :, ::-1] / 255.0
            try:
                cv2.imshow("Press any key to see the next frame...", bgr)
                cv2.waitKey()
            except cv2.error:
                out = Path(self.tmp_path) / f"flow_pred_{i}.png"
                out.parent.mkdir(parents=True, exist_ok=True)
                cv2.imwrite(str(out), (bgr * 255).astype(np.uint8))
                print(f"show_pred: no display; wrote {out}")
