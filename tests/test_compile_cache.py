"""Fleet-shared persistent XLA compile cache (compile_cache.py): keying,
verify-before-trust at the executable level, and the warmup-then-extract
zero-miss contract (ISSUE 11).

Contracts pinned here:
  - the entry key is invariant under NON_SEMANTIC config churn (output
    paths, worker counts, telemetry/fleet/inject switches — cache.py's
    canonicalization, reused verbatim) and under ``resize=auto`` vs its
    resolution, and CHANGES on semantic keys;
  - a jax/jaxlib/backend version change changes the environment
    fingerprint, which resolves to a DIFFERENT entry directory — the
    miss-on-version-change contract (a stale executable can never be
    offered to a new runtime);
  - verify-before-trust: a sealed file whose bytes rotted, and a file a
    crashed writer never sealed, are both DELETED at attach (clean miss,
    recompile) — never handed to the XLA deserializer;
  - warmup-then-extract zero-miss: after ``vft-warmup`` populates the
    triple, a fresh extraction process reports compile-cache hits > 0
    and misses == 0 in its run manifest.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from video_features_tpu import compile_cache as cc

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def no_env_placement(monkeypatch):
    """These tests pass explicit temporary stores; where the machine sets
    JAX_COMPILATION_CACHE_DIR the store would resolve disabled
    (compile_cache.env_placement) — the tests that pin that rule set the
    variable themselves."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("VFT_COMPILE_CACHE_DIR", raising=False)


@pytest.fixture
def cc_detached():
    """Detach the process-global entry around a test and restore JAX's
    compilation-cache config afterwards, so in-process attach tests
    cannot leak state into the rest of the suite."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_compilation_cache_dir
    cc.detach_for_tests()
    yield
    cc.detach_for_tests()
    jax.config.update("jax_compilation_cache_dir", prev)
    compilation_cache.reset_cache()


# -- keying ------------------------------------------------------------------

BASE = {"feature_type": "resnet", "model_name": "resnet18",
        "extraction_fps": 4, "batch_size": 16, "on_extraction": "save_numpy",
        "output_path": "./output", "video_workers": 1, "telemetry": False,
        "compile_cache": True, "compile_cache_dir": None}


@pytest.mark.quick
def test_entry_key_invariant_under_non_semantic_churn(tmp_path):
    _, env_fp = cc.env_fingerprint()
    key = cc.entry_key("resnet", cc.config_fingerprint(BASE), env_fp)
    churned = dict(BASE, output_path=str(tmp_path), video_workers=8,
                   telemetry=True, trace=True, health=True,
                   retry_attempts=5, fleet="queue", fleet_lease_s=5,
                   inject="seed=1;sink.fsync=enospc@n1",
                   compile_cache_dir=str(tmp_path / "cc"),
                   cache=True, cache_dir=str(tmp_path / "fc"))
    assert cc.entry_key("resnet", cc.config_fingerprint(churned),
                        env_fp) == key
    # semantic keys DO key: a different network or frame selection is a
    # different program set
    assert cc.entry_key("resnet", cc.config_fingerprint(
        dict(BASE, model_name="resnet50")), env_fp) != key
    assert cc.entry_key("resnet", cc.config_fingerprint(
        dict(BASE, extraction_fps=2)), env_fp) != key
    # family is its own axis
    assert cc.entry_key("clip", cc.config_fingerprint(BASE),
                        env_fp) != key


@pytest.mark.quick
def test_resolved_overlay_makes_auto_equal_its_resolution():
    # a save-sink run predicts resize=auto -> device: same key as the
    # explicit setting (the feature cache's auto-equivalence, applied
    # pre-construction via the driver-side predictor)
    auto = dict(BASE, resize="auto")
    explicit = dict(BASE, resize="device")
    fp_auto = cc.config_fingerprint(auto, cc.resolved_overlay(auto))
    fp_explicit = cc.config_fingerprint(explicit,
                                        cc.resolved_overlay(explicit))
    assert fp_auto == fp_explicit
    # a print run resolves host — a different program set, different key
    printy = dict(BASE, resize="auto", on_extraction="print")
    assert cc.config_fingerprint(
        printy, cc.resolved_overlay(printy)) != fp_auto


@pytest.mark.quick
def test_env_fingerprint_misses_on_version_change(tmp_path):
    env, fp = cc.env_fingerprint()
    assert env["jax"] and env["backend"] == "cpu"
    assert "cpu_features" in env  # CPU entries are microarch-scoped
    _, fp_jax = cc.env_fingerprint(jax_version="99.0.0")
    _, fp_jaxlib = cc.env_fingerprint(jaxlib_version="99.0.0")
    _, fp_backend = cc.env_fingerprint(backend="tpu", device_kind="v5e")
    assert len({fp, fp_jax, fp_jaxlib, fp_backend}) == 4
    # a changed fingerprint resolves to a DIFFERENT directory: the new
    # runtime starts cold instead of deserializing a stale executable
    cfg = cc.config_fingerprint(BASE)
    dirs = {cc.CompileCacheEntry(str(tmp_path), "resnet", cfg, f).dir
            for f in (fp, fp_jax, fp_jaxlib, fp_backend)}
    assert len(dirs) == 4


# -- verify-before-trust ------------------------------------------------------

def _fake_entry(tmp_path) -> cc.CompileCacheEntry:
    entry = cc.CompileCacheEntry(str(tmp_path / "store"), "resnet",
                                 "c" * 64, "e" * 64)
    os.makedirs(entry.dir, exist_ok=True)
    return entry


@pytest.mark.quick
def test_seal_then_verify_keeps_sealed_files(tmp_path):
    entry = _fake_entry(tmp_path)
    for name in ("jit_a-1111-cache", "jit_b-2222-cache"):
        Path(entry.dir, name).write_bytes(os.urandom(256))
    assert not entry.is_warm()  # unsealed files carry no warm promise
    assert entry.seal() == 2
    assert entry.is_warm()
    assert entry.verify() == {"verified": 2, "dropped": 0}
    assert entry.is_warm()


@pytest.mark.quick
def test_corrupt_sealed_file_dropped_not_served(tmp_path):
    entry = _fake_entry(tmp_path)
    good, bad = "jit_a-1111-cache", "jit_b-2222-cache"
    Path(entry.dir, good).write_bytes(os.urandom(256))
    Path(entry.dir, bad).write_bytes(os.urandom(256))
    entry.seal()
    # bit rot / a torn pre-atomic write: same size, different bytes
    Path(entry.dir, bad).write_bytes(os.urandom(256))
    Path(entry.dir, bad[:-len("-cache")] + "-atime").write_bytes(b"t")
    assert entry.verify() == {"verified": 1, "dropped": 1}
    assert not Path(entry.dir, bad).exists()  # never reaches XLA
    assert not Path(entry.dir,
                    bad[:-len("-cache")] + "-atime").exists()
    assert Path(entry.dir, good).exists()
    # a sealed file is now missing -> the warm promise is off until the
    # recompile re-seals
    assert not entry.is_warm()
    entry.seal()
    assert entry.is_warm()


@pytest.mark.quick
def test_unsealed_file_dropped_at_attach(tmp_path):
    entry = _fake_entry(tmp_path)
    Path(entry.dir, "jit_a-1111-cache").write_bytes(os.urandom(128))
    entry.seal()
    # a writer died mid-run: its file exists but was never sealed —
    # completeness is unprovable, so it is dropped (clean recompile)
    Path(entry.dir, "jit_orphan-9999-cache").write_bytes(os.urandom(128))
    assert entry.verify() == {"verified": 1, "dropped": 1}
    assert not Path(entry.dir, "jit_orphan-9999-cache").exists()


# -- enable/attach semantics --------------------------------------------------

@pytest.mark.quick
def test_resolve_root_semantics(tmp_path, monkeypatch):
    assert cc.resolve_root({"compile_cache": False}) is None
    # auto on the CPU backend without an explicit dir: disabled (tests
    # and casual runs must not grow a store as a side effect)
    assert cc.resolve_root({"compile_cache": "auto"}) is None
    assert cc.resolve_root({"compile_cache": "auto",
                            "compile_cache_dir": str(tmp_path)}) \
        == str(tmp_path)
    # nothing set: the one fixed in-checkout directory
    assert cc.resolve_root({"compile_cache": True}) == cc.default_root() \
        == str(REPO / ".cache" / "xla")
    monkeypatch.setenv("VFT_COMPILE_CACHE_DIR", str(tmp_path / "envroot"))
    assert cc.resolve_root({"compile_cache": True}) \
        == str(tmp_path / "envroot")
    with pytest.raises(ValueError, match="compile_cache"):
        cc.resolve_root({"compile_cache": "bogus"})


@pytest.mark.quick
def test_jax_env_var_places_the_cache(tmp_path, monkeypatch, capsys,
                                      cc_detached):
    """JAX_COMPILATION_CACHE_DIR wins over compile_cache_dir= and
    VFT_COMPILE_CACHE_DIR: attach resolves disabled with ONE printed
    line, and no entry may redirect jax_compilation_cache_dir."""
    import jax
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "placed"))
    monkeypatch.setenv("VFT_COMPILE_CACHE_DIR", str(tmp_path / "envroot"))
    cc._announce_env_placement.cache_clear()
    args = dict(BASE, compile_cache_dir=str(tmp_path / "store"))
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append(k))
    assert cc.resolve_root(args) is None
    assert cc.attach("resnet", args) is None and cc.active() is None
    assert cc.attach_for_args("resnet", args) is None
    out = capsys.readouterr().out
    assert out.count("JAX_COMPILATION_CACHE_DIR") == 1 and "disabled" in out
    assert not (tmp_path / "store").exists()
    with pytest.raises(RuntimeError, match="JAX_COMPILATION_CACHE_DIR"):
        _fake_entry(tmp_path).activate()
    assert not updates
    # an explicit compile_cache=false stays silent: nothing to announce
    cc._announce_env_placement.cache_clear()
    assert cc.resolve_root({"compile_cache": False}) is None
    assert not capsys.readouterr().out


@pytest.mark.quick
def test_attach_is_first_wins_process_global(tmp_path, cc_detached):
    args_a = dict(BASE, compile_cache_dir=str(tmp_path / "store"))
    entry = cc.attach("resnet", args_a)
    assert entry is not None and cc.active() is entry
    assert os.path.isdir(entry.dir)
    # a second attach (another family, another dir) returns the active
    # entry unchanged — JAX holds one cache directory per process
    again = cc.attach("clip", dict(BASE, feature_type="clip",
                                   compile_cache_dir=str(tmp_path / "b")))
    assert again is entry
    info = cc.active_info()
    assert info["family"] == "resnet" and not info["warm_at_attach"]
    cc.detach_for_tests()
    assert cc.active() is None


# -- warmup-then-extract zero-miss (E2E, fresh processes) --------------------

_EXTRACT_WORKER = """\
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
from video_features_tpu.cli import main
main(json.loads(sys.argv[1]))
"""


def _run_manifest_compile_cache(out: Path) -> dict:
    for p in sorted(out.rglob("_run.json")):
        doc = json.loads(p.read_text())
        if doc.get("compile_cache") is not None:
            return doc["compile_cache"]
    return {}


def test_warmup_then_extract_zero_miss(sample_video, tmp_path):
    """vft-warmup populates the triple; a FRESH extraction process over
    the same semantic config must then report hits > 0 and misses == 0 —
    the joining-host promise, proven across real process boundaries."""
    store = tmp_path / "store"
    overrides = {"model_name": "resnet18", "device": "cpu",
                 "allow_random_weights": True, "extraction_total": 6,
                 "batch_size": 8, "compile_cache": True,
                 "compile_cache_dir": str(store),
                 "video_paths": str(sample_video)}
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # no_env_placement applies
    warm = subprocess.run(
        [sys.executable, "-c", cc._WARMUP_WORKER, "resnet",
         json.dumps(overrides)], capture_output=True, text=True, env=env,
        timeout=300)
    assert warm.returncode == 0, warm.stderr[-2000:]
    result = json.loads([ln for ln in warm.stdout.splitlines()
                         if ln.startswith("VFT_WARMUP_RESULT ")][-1]
                        [len("VFT_WARMUP_RESULT "):])
    assert result["status"] == "ok", result
    assert result["sealed_files"] > 0
    assert not result["warm_before"]

    argv = ["feature_type=resnet", "model_name=resnet18", "device=cpu",
            "allow_random_weights=true", "on_extraction=save_numpy",
            "extraction_total=6", "batch_size=8", "telemetry=true",
            "compile_cache=true", f"compile_cache_dir={store}",
            f"output_path={tmp_path / 'out'}",
            f"tmp_path={tmp_path / 'tmp'}",
            f"video_paths=[{sample_video}]"]
    run = subprocess.run(
        [sys.executable, "-c", _EXTRACT_WORKER, json.dumps(argv)],
        capture_output=True, text=True, env=env, timeout=300)
    assert run.returncode == 0, (run.stdout + run.stderr)[-2000:]
    assert "compile cache: entry" in run.stdout and "warm" in run.stdout
    summary = _run_manifest_compile_cache(tmp_path / "out")
    assert summary.get("misses", 0) == 0, summary
    assert summary.get("hits", 0) > 0, summary
    assert summary.get("warm_at_attach") is True
