"""Frame-wise extraction pipeline (ResNet, CLIP).

Re-design of reference models/_base/base_framewise_extractor.py:11-88 around a
static-shape jitted device step:

  host:   cv2 stream -> per-frame PIL resize/crop -> uint8 HWC frames
  device: fixed-(B,H,W,3) uint8 batch -> /255 -> normalize -> backbone -> (B,D)

The uint8 H2D transfer is 4x smaller than shipping float32 (HBM/PCIe
bandwidth is the usual bottleneck); scaling and normalization are fused by XLA
into the first conv. Ragged final batches are padded to the fixed shape and
the padded rows dropped on host, so only one executable is compiled per video
resolution. The batch axis is sharded over the mesh's data axis
(parallel/mesh.py), which is this framework's replacement for the reference's
"one process per GPU" scale-out.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional

import numpy as np

from ..config import Config
from ..parallel.mesh import DataParallelApply
from ..telemetry import trace
from ..utils.io import Prefetcher, VideoSource
from .base import BaseExtractor


class FrameWiseExtractor(BaseExtractor):
    """Generic frame-wise driver; families plug in transform + device fn.

    Subclasses set:
      - ``self.host_transform(rgb HWC uint8) -> HWC uint8`` (resize+crop)
      - ``self.runner`` (DataParallelApply over the backbone)
      - ``self.maybe_show_pred(feats np.ndarray)``
    """

    #: wire formats: uint8 is the default AND the parity path (PIL resize
    #: outputs uint8, so nothing is lost); 'yuv420' opts into packed I420 at
    #: 1.5 bytes/pixel with colorspace conversion on device (H2D-bound hosts)
    supported_ingest = ("uint8", "yuv420")

    def __init__(self, args: Config) -> None:
        super().__init__(args)
        self.model_name = args.get("model_name")
        self.batch_size = int(args.batch_size)
        self.extraction_fps = args.get("extraction_fps")
        self.extraction_total = args.get("extraction_total")
        self.output_feat_keys = [self.feature_type, "fps", "timestamps_ms"]
        self.host_transform: Optional[Callable] = None
        self.runner: Optional[DataParallelApply] = None
        self.ingest = self._resolve_ingest(args, "uint8")
        #: resize=device (the default for save runs since the defaults
        #: flip, via resize=auto) moves the dominant host cost — PIL's
        #: antialiased filtering, ~1.3 ms/frame vs ~0.34 ms of cv2 decode —
        #: onto the MXU as two coefficient matmuls (ops/preprocess.py
        #: device_resize, within 2 LSB of PIL). The host then only decodes;
        #: raw frames ship as decoder-native uint8 BGR (3 B/px) or, under
        #: ingest=yuv420, as packed I420 planes (1.5 B/px) with the BT.601
        #: conversion fused on device in front of the resize
        #: (ops/colorspace.py). Subclasses declare resize_spec/crop_size/
        #: base_fwd/runner_builder to opt in.
        self.resize_mode = self._resolve_resize_mode(args)
        self.resize_spec = None  # (size, interpolation, to_smaller_edge)
        self.crop_size: Optional[int] = None
        self.base_fwd: Optional[Callable] = None
        self.runner_builder: Optional[Callable] = None

    def encode_wire_u8(self, u8: np.ndarray) -> np.ndarray:
        """uint8 HWC frame -> the configured wire format (transform tail)."""
        if self.ingest == "uint8":
            return u8
        from ..ops import colorspace
        return colorspace.rgb_to_yuv420(u8)

    def _device_resize_runner(self, in_h: int, in_w: int,
                              packed: bool = False) -> DataParallelApply:
        """Per-source-resolution runner: PIL-coefficient resize + center crop
        fused in front of the family's device forward. Cached so each
        resolution compiles once (same executable-per-resolution economy as
        the host path); all runners share the committed device param arrays
        (DataParallelApply's device_put of an already-committed tree with the
        same sharding is a no-op), so weights live in HBM once.

        ``packed`` (ingest=yuv420): the wire carries (in_h*3/2, in_w)
        packed I420 planes; the fused program prepends the BT.601 I420->RGB
        conversion (ops/colorspace.py, rounded back onto the uint8 lattice)
        to the resize."""
        def build():
            from ..ops import preprocess as pp
            size, interp, smaller = self.resize_spec
            if isinstance(size, int):
                ow, oh = pp.resize_edge_size(in_w, in_h, size, smaller)
            else:
                oh, ow = size
            resize = pp.make_device_resizer(in_h, in_w, oh, ow, interp)
            c = self.crop_size
            i, j = pp.center_crop_offsets(oh, ow, c, c)
            base = self.base_fwd

            if packed:
                from ..ops import colorspace

                def fwd(params, packed_u8):
                    # 1.5 B/px I420 wire: YUV->RGB, resize and crop all
                    # fuse into one device program in front of the
                    # backbone; the host never converts or resizes
                    rgb = colorspace.yuv420_frame_to_rgb_u8(
                        packed_u8, in_h, in_w)
                    x = resize(rgb)
                    return base(params, x[:, i:i + c, j:j + c, :])
            else:
                def fwd(params, raw_u8):
                    # frames arrive decoder-native BGR (channel_order
                    # below): the RGB reorder is a reversed gather XLA
                    # fuses into the resize matmul's input read — the host
                    # never runs a full-resolution cvtColor in this mode
                    x = resize(raw_u8[..., ::-1])
                    return base(params, x[:, i:i + c, j:j + c, :])

            return self.runner_builder(fwd)

        return self._cached_resize_runner((in_h, in_w, packed), build)

    def _wire_order(self, video_path: str) -> str:
        """Delivery format for resize=device: decoder-native BGR, or packed
        I420 under ingest=yuv420 (halving the raw wire again). I420 needs
        even frame dims; odd sources fall back to the BGR raw wire for
        that video — same features, 2x the bytes."""
        if self.ingest != "yuv420":
            return "bgr"
        from ..utils.io import get_video_props
        props = get_video_props(video_path)
        if props["height"] % 2 or props["width"] % 2:
            print(f"WARNING: {video_path} has odd dimensions "
                  f"{props['height']}x{props['width']}; I420 needs even "
                  "dims — shipping raw BGR for this video instead")
            return "bgr"
        return "i420"

    def extract(self, video_path: str) -> Dict[str, np.ndarray]:
        device_resize = self.resize_mode == "device"
        wire_order = self._wire_order(video_path) if device_resize else "rgb"
        video = self.video_source(
            video_path,
            batch_size=self.batch_size,
            fps=self.extraction_fps,
            total=self.extraction_total,
            # device_resize: host ships raw decoded frames — decoder-
            # native BGR (the reorder rides the device resize for free)
            # or packed I420 planes under ingest=yuv420
            transform=None if device_resize else self.host_transform,
            channel_order=wire_order,
        )
        vid_feats: List[np.ndarray] = []
        timestamps_ms: List[float] = []
        # decode-ahead: the next batch decodes while this one is on-device;
        # batches are dispatched asynchronously and materialized at the end
        # (no per-batch D2H stall) unless show_pred needs per-batch values
        stream = None
        for batch, times, _ in Prefetcher(video):
            if stream is None:
                # the resize matrices come from the first *decoded* frame's
                # shape — container metadata can disagree with it (e.g.
                # rotation tags auto-applied by cv2). Packed I420 frames
                # are (H*3/2, W); recover the true source height.
                if device_resize:
                    fh, fw = batch[0].shape[:2]
                    packed = wire_order == "i420"
                    if packed:
                        fh = fh * 2 // 3
                    runner = self._device_resize_runner(fh, fw, packed)
                else:
                    runner = self.runner
                stream = self.feature_stream(
                    runner,
                    on_result=lambda feats, ctx: self.maybe_show_pred(feats))
            # runner pads ragged tails to fixed_batch
            with trace.span("batch.assemble", rows=len(batch)):
                group = np.stack(batch)
            stream.submit(group)
            timestamps_ms.extend(times)
        done = stream.finish() if stream is not None else []
        with trace.span("batch.collect", batches=len(done)):
            for bi, feats in enumerate(done):
                if self.parity:
                    # backbone seam: the per-batch activations exactly as
                    # they come off the device runner
                    from ..telemetry import parity as _parity
                    _parity.tap("backbone", self.feature_type, feats,
                                video=str(video_path),
                                feature_type=self.feature_type, index=bi)
                vid_feats.extend(list(feats))
            collected = np.array(vid_feats)
        return {
            self.feature_type: collected,
            "fps": np.array(video.fps),
            "timestamps_ms": np.array(timestamps_ms),
        }

    def maybe_show_pred(self, feats: np.ndarray) -> None:
        pass
