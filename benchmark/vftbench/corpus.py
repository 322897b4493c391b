"""Seeded video corpora: a plan (which video has how many frames) and the
files, synthesised with ``cv2.VideoWriter`` and kept in the checkout.

The plan is a fixed amount of work: the durations are the mid-quantiles of
the mix's distribution, the same multiset for every seed, and the seed
assigns them to videos and draws every video's content. Runs of one cell
then differ by what the system does, not by how much they were given.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import zlib
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


def stream(seed: int, *names: Any) -> np.random.Generator:
    """A named random stream: independent of every other name, stable across
    runs and machines (PCG64 seeded from the seed and a CRC of the names)."""
    tag = zlib.crc32(":".join(str(n) for n in names).encode())
    return np.random.default_rng(np.random.SeedSequence([int(seed), tag]))


def quantile(dist: Dict[str, Any], q: float) -> float:
    """Inverse CDF of a duration distribution at ``q``, clipped to its
    ``min``/``max``."""
    kind = dist["dist"]
    if kind == "lognormal":
        v = float(dist["median"]) * float(
            np.exp(float(dist["sigma"]) * NormalDist().inv_cdf(q)))
    elif kind == "uniform":
        v = float(dist["min"]) + q * (float(dist["max"]) - float(dist["min"]))
    else:
        raise ValueError(f"unknown duration distribution {kind!r}")
    return min(max(v, float(dist.get("min", v))), float(dist.get("max", v)))


def plan(spec: Dict[str, Any], seed: int) -> List[Dict[str, Any]]:
    """``[{"name", "frames"}]`` for the corpus ``spec`` under ``seed``."""
    n = int(spec["videos"])
    frames = [int(round(quantile(spec["duration_s"], (i + 0.5) / n)
                        * float(spec["fps"]))) for i in range(n)]
    order = stream(seed, spec["owner"], "durations").permutation(n)
    return [{"name": f"v{i:02d}", "frames": frames[int(order[i])]}
            for i in range(n)]


def units_of(frames: int, unit: Dict[str, Any]) -> int:
    """Units the program yields from ``frames`` frames: windows of
    ``window`` frames every ``stride`` frames, a trailing partial dropped."""
    window, stride = int(unit["window"]), int(unit["stride"])
    return 0 if frames < window else (frames - window) // stride + 1


def frames_for(units: int, unit: Dict[str, Any]) -> int:
    return int(unit["window"]) + (int(units) - 1) * int(unit["stride"])


# -- synthesis ----------------------------------------------------------------

def _texture(rng: np.random.Generator, h: int, w: int) -> np.ndarray:
    """Smooth colour texture with detail at two scales, ``(h, w, 3)`` uint8."""
    import cv2
    coarse = rng.integers(0, 256, (h // 24 + 2, w // 24 + 2, 3), np.uint8)
    fine = rng.integers(0, 256, (h // 4 + 2, w // 4 + 2, 3), np.uint8)
    a = cv2.resize(coarse, (w, h), interpolation=cv2.INTER_CUBIC)
    b = cv2.resize(fine, (w, h), interpolation=cv2.INTER_CUBIC)
    return cv2.addWeighted(a, 0.65, b, 0.35, 0.0)


def write_video(path: Path, frames: int, spec: Dict[str, Any],
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


def _spec_key(spec: Dict[str, Any]) -> str:
    return hashlib.sha256(json.dumps(spec, sort_keys=True).encode()
                          ).hexdigest()[:8]


def _materialize(final: Path, entries: List[Dict[str, Any]],
                 spec: Dict[str, Any], seed: int, tag: str) -> Path:
    """Write ``entries`` under ``final`` unless a finished copy is there.
    Built in a sibling directory and renamed, so a killed run leaves nothing
    that looks finished."""
    marker = final / "plan.json"
    want = json.dumps(entries, sort_keys=True)
    if marker.is_file() and marker.read_text() == want:
        return final
    shutil.rmtree(final, ignore_errors=True)
    building = final.with_name(final.name + ".building")
    shutil.rmtree(building, ignore_errors=True)
    building.mkdir(parents=True)

    def one(entry: Dict[str, Any]) -> None:
        write_video(building / f"{entry['name']}.mp4", entry["frames"], spec,
                    stream(seed, tag, entry["name"], entry["frames"]))

    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        for result in pool.map(one, entries):
            pass  # reading every result raises what a writer raised
    (building / "plan.json").write_text(want)
    os.rename(building, final)
    return final


def corpus_dir(out_root: Path, spec: Dict[str, Any], seed: int) -> Path:
    return Path(out_root) / "corpus" / \
        f"{spec['owner']}-s{int(seed)}-{_spec_key(spec)}"


def build(out_root: Path, spec: Dict[str, Any], seed: int
          ) -> List[Dict[str, Any]]:
    """The seeded corpus on disk: the plan with a ``path`` for every video."""
    entries = plan(spec, seed)
    root = _materialize(corpus_dir(out_root, spec, seed), entries, spec,
                        seed, spec["owner"])
    return [{**e, "path": str(root / f"{e['name']}.mp4")} for e in entries]


def build_fixed(out_root: Path, spec: Dict[str, Any], frame_counts: List[int]
                ) -> Dict[int, str]:
    """Seed-independent videos of the given lengths (warm-up and the check
    video): the same files for every run of every seed."""
    geometry = {k: spec[k] for k in ("width", "height", "fps")}
    geometry["codec"] = spec.get("codec", "mp4v")
    entries = [{"name": f"f{n:05d}", "frames": int(n)}
               for n in sorted(set(frame_counts))]
    key = _spec_key({**geometry, "frames": [e["frames"] for e in entries]})
    root = _materialize(Path(out_root) / "corpus" / f"fixed-{key}", entries,
                        geometry, 0, "fixed")
    return {e["frames"]: str(root / f"{e['name']}.mp4") for e in entries}
