"""The ``video`` corpus kind: mp4 files synthesised with ``cv2.VideoWriter``.
A frame is a video frame and ``fps`` the frame rate.
"""
from pathlib import Path
from typing import Any, Dict

import numpy as np

SUFFIX = ".mp4"
#: what makes a fixed file what it is: key -> default (None: no default)
GEOMETRY = {"width": None, "height": None, "fps": None, "codec": "mp4v"}


def _texture(rng: np.random.Generator, h: int, w: int) -> np.ndarray:
    """Smooth colour texture with detail at two scales, ``(h, w, 3)`` uint8."""
    import cv2
    coarse = rng.integers(0, 256, (h // 24 + 2, w // 24 + 2, 3), np.uint8)
    fine = rng.integers(0, 256, (h // 4 + 2, w // 4 + 2, 3), np.uint8)
    a = cv2.resize(coarse, (w, h), interpolation=cv2.INTER_CUBIC)
    b = cv2.resize(fine, (w, h), interpolation=cv2.INTER_CUBIC)
    return cv2.addWeighted(a, 0.65, b, 0.35, 0.0)


def write(path: Path, frames: int, spec: Dict[str, Any],
          rng: np.random.Generator) -> None:
    """One video of ``frames`` frames: a textured background that drifts, a
    textured patch that crosses it on its own path, and fresh noise on every
    frame, so that decoding is not trivial and the flow is not zero."""
    import cv2
    w, h, fps = int(spec["width"]), int(spec["height"]), float(spec["fps"])
    margin = 48
    bg = _texture(rng, h + 2 * margin, w + 2 * margin)
    ph, pw = h // 3, w // 4
    patch = _texture(rng, ph, pw)
    noise = rng.integers(0, 13, (8, h, w, 3), np.uint8)
    phase = rng.uniform(0, 2 * np.pi, 4)
    speed = rng.uniform(0.03, 0.09, 4)
    writer = cv2.VideoWriter(str(path), cv2.VideoWriter_fourcc(
        *str(spec.get("codec", "mp4v"))), fps, (w, h))
    if not writer.isOpened():
        raise RuntimeError(f"cv2 cannot open a {spec.get('codec', 'mp4v')} "
                           f"writer for {path}")
    try:
        for t in range(frames):
            ox = margin + int(round(0.9 * margin * np.sin(
                speed[0] * t + phase[0])))
            oy = margin + int(round(0.9 * margin * np.cos(
                speed[1] * t + phase[1])))
            frame = bg[oy:oy + h, ox:ox + w].copy()
            px = int(round((w - pw) * (0.5 + 0.5 * np.sin(
                speed[2] * t + phase[2]))))
            py = int(round((h - ph) * (0.5 + 0.5 * np.cos(
                speed[3] * t + phase[3]))))
            frame[py:py + ph, px:px + pw] = patch
            cv2.add(frame, noise[int(rng.integers(0, 8))], dst=frame)
            writer.write(frame)
    finally:
        writer.release()
