"""Picklable host-transform callables, one per family shape.

The extractors' host transforms used to be closures over ``self`` — fine
in-process, but ``video_decode=process`` (utils/io.py ProcessVideoSource)
ships the transform to a spawned decode worker via pickle, and a closure
cannot cross that boundary. These classes are the same functions as plain
data + ``__call__``; the extractors now build instances of them, so the
in-process and process-decode paths run literally identical code.

Deliberately light imports (numpy / PIL / cv2 through ops.preprocess and
ops.colorspace): unpickling in a decode worker must not drag jax/flax in —
the worker only decodes and transforms, and a chip belongs to one process:
a jax op in a child would contend for the one the parent holds.
"""
from __future__ import annotations

import numpy as np

from ..telemetry import trace  # no jax behind it; no-op spans when off
from . import colorspace
from . import preprocess as pp


def encode_wire(x01: np.ndarray, ingest: str) -> np.ndarray:
    """[0, 1] float HWC frame -> wire format (clip-stack families' tail)."""
    if ingest == "float32":
        return x01
    u8 = pp.quantize_u8(x01)
    if ingest == "uint8":
        return u8
    return colorspace.rgb_to_yuv420(u8)


def encode_wire_u8(u8: np.ndarray, ingest: str) -> np.ndarray:
    """uint8 HWC frame -> wire format (frame-wise families' tail)."""
    if ingest == "uint8":
        return u8
    return colorspace.rgb_to_yuv420(u8)


class R21DTransform:
    """Decoder-native BGR frame -> 112px wire clip frame (extractors/r21d).

    float/resize/crop are channel-independent, so the RGB reorder happens
    on the 112px crop — 6x fewer pixels than a full-resolution cvtColor,
    bit-identical result (frame_channel_order='bgr' contract)."""

    def __init__(self, ingest: str):
        self.ingest = ingest

    def __call__(self, bgr: np.ndarray) -> np.ndarray:
        with trace.span("decode.resize"):
            x = bgr.astype(np.float32) / 255.0
            x = pp.bilinear_resize_no_antialias(x, (128, 171))
        with trace.span("decode.ingest"):
            x = np.ascontiguousarray(pp.center_crop(x, 112)[:, :, ::-1])
            return encode_wire(x, self.ingest)


class S3DTransform:
    """Decoder-native BGR frame -> 224px wire clip frame (extractors/s3d);
    same deferred-reorder contract as R21DTransform."""

    def __init__(self, ingest: str):
        self.ingest = ingest

    def __call__(self, bgr: np.ndarray) -> np.ndarray:
        with trace.span("decode.resize"):
            x = bgr.astype(np.float32) / 255.0
            scale = 224.0 / min(x.shape[0], x.shape[1])
            x = pp.bilinear_resize_by_scale(x, scale)
        with trace.span("decode.ingest"):
            x = np.ascontiguousarray(pp.center_crop(x, 224)[:, :, ::-1])
            return encode_wire(x, self.ingest)


class ResizeCropTransform:
    """RGB frame -> PIL resize + center crop -> uint8 wire (resnet: 256->
    224 bilinear; clip: R->R bicubic)."""

    def __init__(self, size: int, crop: int, interpolation: str,
                 ingest: str):
        self.size = size
        self.crop = crop
        self.interpolation = interpolation
        self.ingest = ingest

    def __call__(self, rgb: np.ndarray) -> np.ndarray:
        with trace.span("decode.resize"):
            out = pp.pil_resize(rgb, self.size,
                                interpolation=self.interpolation)
        with trace.span("decode.ingest"):
            return encode_wire_u8(pp.center_crop(out, self.crop),
                                  self.ingest)


class MinSideResize:
    """RGB frame -> smaller-edge PIL bilinear resize, kept uint8 (the i3d
    host path; reference extract_i3d.py:41-46)."""

    def __init__(self, min_side: int):
        self.min_side = min_side

    def __call__(self, rgb: np.ndarray) -> np.ndarray:
        return pp.pil_resize(rgb, self.min_side)
