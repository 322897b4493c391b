"""Run manifest: ``{output_path}/_run.json``, written once at exit.

The manifest makes a run auditable and reproducible from its artifacts
alone: the exact config it ran with, the code version (git commit +
package versions), the hardware it saw (device/mesh topology,
parallel/mesh.py), what it did (tally, per-stage aggregates, metrics
dump) and what the XLA compile cache contributed (hit/miss counts —
the visibility PAPERS.md's compiler-first inference work argues is a
prerequisite for any principled perf claim). Written via atomic replace
(telemetry/jsonl.py) so a preempted exit never leaves a torn document.
"""
from __future__ import annotations

import os
import socket
import subprocess
import sys
import time
from typing import Any, Dict, Optional

from . import startup

MANIFEST_SCHEMA_VERSION = "vft.run_manifest/1"
MANIFEST_FILENAME = "_run.json"


def _git_describe(cwd: Optional[str] = None) -> Dict[str, Any]:
    """Best-effort commit + dirty flag; a worker outside a checkout (pip
    install, docker) reports ``unknown`` rather than failing the run."""
    try:
        root = cwd or os.path.dirname(os.path.abspath(__file__))
        rev = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=root, capture_output=True,
            text=True, timeout=5)
        if rev.returncode != 0:
            return {"commit": "unknown"}
        dirty = subprocess.run(
            ["git", "status", "--porcelain"], cwd=root, capture_output=True,
            text=True, timeout=5)
        return {"commit": rev.stdout.strip(),
                "dirty": bool(dirty.stdout.strip())
                if dirty.returncode == 0 else None}
    except Exception:
        return {"commit": "unknown"}


def _versions() -> Dict[str, str]:
    out = {"python": sys.version.split()[0]}
    from .. import __version__
    out["video_features_tpu"] = __version__
    for mod in ("jax", "jaxlib", "flax", "numpy", "cv2", "yaml"):
        try:
            m = __import__(mod)
            out[mod] = str(getattr(m, "__version__", "?"))
        except Exception:
            out[mod] = "absent"
    # which .npy writer the sinks used (native/__init__.py): the g++-built
    # one or its byte-identical Python fallback
    from .. import native
    out["native_writer"] = native.status()
    return out


def _topology() -> Dict[str, Any]:
    """Device/mesh topology via parallel/mesh.py; defensive — a manifest
    must still be written when the backend is torn down or absent."""
    try:
        from ..parallel.mesh import mesh_topology
        return mesh_topology()
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}


def build_manifest(*,
                   run_config: Optional[dict] = None,
                   feature_type: Optional[str] = None,
                   host_id: Optional[str] = None,
                   run_id: Optional[str] = None,
                   started_time: Optional[float] = None,
                   wall_s: Optional[float] = None,
                   tally: Optional[Dict[str, int]] = None,
                   failure_tallies: Optional[Dict[str, int]] = None,
                   stage_totals: Optional[Dict[str, Any]] = None,
                   metrics_dump: Optional[dict] = None,
                   compile_cache: Optional[Dict[str, int]] = None,
                   health: Optional[Dict[str, Dict[str, int]]] = None,
                   roofline: Optional[dict] = None,
                   ) -> dict:
    done = (tally or {}).get("done", 0)
    return {
        "schema": MANIFEST_SCHEMA_VERSION,
        "feature_type": feature_type,
        "host": socket.gethostname(),
        "host_id": host_id,
        # matches the run_id in this run's heartbeats; report tools use it
        # to ignore stale heartbeat files from a prior run of the same dir
        "run_id": run_id,
        "pid": os.getpid(),
        "argv": list(sys.argv),
        "started_time": started_time,
        "finished_time": round(time.time(), 3),
        "wall_s": None if wall_s is None else round(float(wall_s), 3),
        "videos_per_s": (round(done / wall_s, 4)
                         if wall_s and done else None),
        "tally": dict(tally or {}),
        "failure_tallies": dict(failure_tallies or {}),
        "stage_totals": dict(stage_totals or {}),
        "compile_cache": dict(compile_cache or {}),
        # the process's own account of its start (telemetry/startup.py):
        # seconds by phase, programs compiled or loaded, the cache's share
        "startup": startup.summary(),
        # output-health roll-up (telemetry/health.py): per-family digest
        # record + NaN/Inf totals; {} when health=false (nothing observed)
        "health": dict(health or {}),
        # roofline accounting (telemetry/roofline.py): the run's final
        # per-family MFU/verdict document; {} when roofline=false
        "roofline": dict(roofline or {}),
        "config": dict(run_config or {}),
        "versions": _versions(),
        "git": _git_describe(),
        "topology": _topology(),
        "metrics": metrics_dump or {"series": []},
    }
