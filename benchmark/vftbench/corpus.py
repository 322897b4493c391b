"""Seeded corpora: a plan (which file has how many frames) and the files,
written by the corpus's kind (``benchmark/corpora/<kind>.py``, which
``manifest.Cell.corpus_kind`` loads; ``video`` where the block names none)
and kept in the checkout. A "frame" is the item the kind counts (a video
frame, an audio sample, a token), ``fps`` its rate, and a unit's ``window``
and ``stride`` are in those items.

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
from types import ModuleType
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


# -- files --------------------------------------------------------------------

def _spec_key(spec: Dict[str, Any]) -> str:
    return hashlib.sha256(json.dumps(spec, sort_keys=True).encode()
                          ).hexdigest()[:8]


def _materialize(final: Path, entries: List[Dict[str, Any]],
                 spec: Dict[str, Any], seed: int, tag: str,
                 kind: ModuleType) -> Path:
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
        kind.write(building / f"{entry['name']}{kind.SUFFIX}",
                   entry["frames"], spec,
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


def build(out_root: Path, spec: Dict[str, Any], seed: int, kind: ModuleType
          ) -> List[Dict[str, Any]]:
    """The seeded corpus on disk: the plan with a ``path`` for every file."""
    entries = plan(spec, seed)
    root = _materialize(corpus_dir(out_root, spec, seed), entries, spec,
                        seed, spec["owner"], kind)
    return [{**e, "path": str(root / f"{e['name']}{kind.SUFFIX}")}
            for e in entries]


def build_fixed(out_root: Path, spec: Dict[str, Any], frame_counts: List[int],
                kind: ModuleType) -> Dict[int, str]:
    """Seed-independent files of the given lengths (warm-up and the check
    input): the same files for every run of every seed. What makes such a
    file what it is are the kind's ``GEOMETRY`` keys (``None``: the block
    has to give it) and, where the block names one, its ``kind``."""
    geometry = {k: spec[k] if default is None else spec.get(k, default)
                for k, default in kind.GEOMETRY.items()}
    if "kind" in spec:
        geometry["kind"] = spec["kind"]
    entries = [{"name": f"f{n:05d}", "frames": int(n)}
               for n in sorted(set(frame_counts))]
    key = _spec_key({**geometry, "frames": [e["frames"] for e in entries]})
    root = _materialize(Path(out_root) / "corpus" / f"fixed-{key}", entries,
                        geometry, 0, "fixed", kind)
    return {e["frames"]: str(root / f"{e['name']}{kind.SUFFIX}")
            for e in entries}
