#!/usr/bin/env python
"""Compile-cache quick-gate: the fleet-shared XLA store's cross-process
contract, proven on real process boundaries (ISSUE 11).

Sibling of check_cache_smoke.py, for compile_cache.py. Three COLD
processes of the same family share one store:

  1. run 1 (empty store): compiles — manifest ``compile_cache`` reports
     misses > 0 — and seals the entry on exit;
  2. run 2 (fresh output dir, same triple): attaches WARM — hits > 0,
     misses == 0 (the joining-host zero-miss promise) — and its features
     are byte-identical to run 1's (a deserialized executable that
     computed different bytes would be the cross-host hazard the
     environment fingerprint exists to prevent);
  3. a sealed cache file is then CORRUPTED in place: run 3 must drop it
     at attach (verify-before-trust), recompile cleanly (misses > 0
     again, features still byte-identical) and re-seal — afterwards the
     re-stored file verifies against the new sums.

Exit 0 = contract holds; exit 1 = every violation listed. Runs in the
CI quick tier (.github/workflows/ci.yml); the in-suite twin is
tests/test_compile_cache.py, and ``python bench.py bench_coldstart``
measures the same shape as a latency ratio.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
from pathlib import Path
from typing import List

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# this gate drives the store through an explicit temporary compile_cache_dir;
# where JAX_COMPILATION_CACHE_DIR is set the store resolves disabled
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

SAMPLE = REPO_ROOT / "tests" / "assets" / "v_synth_sample.mp4"

_WORKER = """\
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
from video_features_tpu.cli import main
main(json.loads(sys.argv[1]))
"""


def _run(td: Path, out: str, video: Path) -> subprocess.CompletedProcess:
    argv = ["feature_type=resnet", "model_name=resnet18", "device=cpu",
            "allow_random_weights=true", "on_extraction=save_numpy",
            "extraction_total=6", "batch_size=8", "telemetry=true",
            "compile_cache=true", f"compile_cache_dir={td / 'store'}",
            f"output_path={td / out}", f"tmp_path={td / 'tmp'}",
            f"video_paths=[{video}]"]
    return subprocess.run(
        [sys.executable, "-c", _WORKER, json.dumps(argv)],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))


def _manifest_cc(out: Path) -> dict:
    for p in sorted(out.rglob("_run.json")):
        doc = json.loads(p.read_text())
        if doc.get("compile_cache") is not None:
            return doc["compile_cache"]
    return {}


def _npy_shas(out: Path) -> dict:
    return {str(p.relative_to(out)): hashlib.sha256(
        p.read_bytes()).hexdigest() for p in sorted(out.rglob("*.npy"))}


def check(td: Path) -> List[str]:
    errs: List[str] = []
    video = td / "smoke.mp4"
    shutil.copy(SAMPLE, video)

    # -- run 1: cold store, compiles + seals --------------------------------
    p1 = _run(td, "p1", video)
    if p1.returncode != 0:
        return [f"run 1 failed: {(p1.stdout + p1.stderr)[-1500:]}"]
    cc1 = _manifest_cc(td / "p1")
    if not int(cc1.get("misses", 0)):
        errs.append(f"run 1 (empty store) reported no compile-cache "
                    f"misses: {cc1!r}")
    entry_dirs = [p.parent for p in (td / "store").rglob("_entry.json")]
    if len(entry_dirs) != 1:
        return errs + [f"expected exactly 1 sealed entry, found "
                       f"{len(entry_dirs)}"]
    entry = entry_dirs[0]
    # corrupt the LARGEST sealed executable: the family's forward
    # program, the one every run must request (the many small sealed
    # files are init-time helpers a warm run may never re-request)
    sealed = sorted((n for n in os.listdir(entry)
                     if n.endswith("-cache")),
                    key=lambda n: (entry / n).stat().st_size)
    if not sealed:
        errs.append("run 1 sealed an entry with no cache files")

    # -- run 2: warm attach, zero-miss, bit-identical -----------------------
    p2 = _run(td, "p2", video)
    if p2.returncode != 0:
        return errs + [f"run 2 failed: {(p2.stdout + p2.stderr)[-1500:]}"]
    cc2 = _manifest_cc(td / "p2")
    if not int(cc2.get("hits", 0)):
        errs.append(f"run 2 (sealed store) reported no hits: {cc2!r}")
    if int(cc2.get("misses", 0)):
        errs.append(f"run 2 recompiled despite the warm entry: {cc2!r}")
    if cc2.get("warm_at_attach") is not True:
        errs.append(f"run 2 manifest lacks warm_at_attach=true: {cc2!r}")
    sha1, sha2 = _npy_shas(td / "p1"), _npy_shas(td / "p2")
    if not sha1 or sha1 != sha2:
        errs.append(f"run 2 features not byte-identical to run 1 "
                    f"({len(sha1)} vs {len(sha2)} artifacts)")

    # -- run 3: corrupt a sealed file -> dropped, clean recompile, re-seal --
    victim = entry / sealed[-1]
    victim.write_bytes(os.urandom(max(64, victim.stat().st_size // 2)))
    p3 = _run(td, "p3", video)
    if p3.returncode != 0:
        return errs + [f"run 3 (corrupted entry) failed instead of "
                       f"recompiling: {(p3.stdout + p3.stderr)[-1500:]}"]
    if "compile cache: dropped" not in (p3.stdout + p3.stderr):
        errs.append("run 3 never reported dropping the corrupted file")
    cc3 = _manifest_cc(td / "p3")
    if not int(cc3.get("misses", 0)):
        errs.append(f"run 3 reported no misses after the corruption — "
                    f"did it serve the corrupt executable? {cc3!r}")
    sha3 = _npy_shas(td / "p3")
    if sha1 != sha3:
        errs.append("run 3 features not byte-identical after recompile")
    # re-stored + re-sealed: the victim file verifies against fresh sums
    sums = json.loads((entry / "_sums.json").read_text())["files"]
    if not victim.exists():
        errs.append("run 3 did not re-store the recompiled executable")
    elif sealed[-1] not in sums or hashlib.sha256(
            victim.read_bytes()).hexdigest() != sums[sealed[-1]]["sha256"]:
        errs.append("re-stored executable does not verify against the "
                    "re-sealed sums")
    return errs


def main() -> int:
    if not SAMPLE.exists():
        print(f"SKIP: vendored sample missing ({SAMPLE})")
        return 0
    with tempfile.TemporaryDirectory(prefix="vft_cc_smoke_") as td:
        errs = check(Path(td))
    if errs:
        print("COMPILE CACHE SMOKE: FAIL")
        for e in errs:
            print(f"  - {e}")
        return 1
    print("COMPILE CACHE SMOKE: OK (cold compile+seal, warm zero-miss "
          "bit-identical, corrupt entry dropped + re-stored)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
