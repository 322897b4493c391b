"""Fleet-shared persistent XLA compile cache: never compile twice, anywhere.

PR 7 made repeat *data* work free (cache.py) and PR 8 made fleet
membership dynamic (parallel/queue.py), but a joining or restarted host
still paid the full XLA compile before its first claim — elasticity in
name only, because scaling up was slow by construction. JAX already
ships a persistent compilation cache (one directory of serialized
executables, keyed per-program by XLA), and cli.py has pointed it at a
per-machine directory since round 1. What that leaves unsolved at fleet
scale:

  - **sharing is unsafe unverified**: a shared directory mixes entries
    from every jax/jaxlib/libtpu combination (deserialization failures,
    or worse: XLA:CPU executables bake in the compiling host's CPU
    features — a cross-microarch hit can SIGILL);
  - **nothing is content-addressed**: there is no name for "the warm set
    of family X under config Y on runtime Z", so a joining host cannot
    know — let alone promise — that it will compile nothing;
  - **nothing verifies**: a torn or bit-rotted entry is handed straight
    to the XLA deserializer.

This module wraps JAX's cache in the same discipline the feature cache
proved out:

  **entry** = one directory per ``(family, config fingerprint,
  environment fingerprint)`` triple at
  ``{root}/{family}/{key[:2]}/{key}/``, where

    - the **config fingerprint** reuses cache.py's canonicalization:
      NON_SEMANTIC_KEYS dropped, the extractor's resolved
      ``resize_mode``/``ingest`` overlaid — two configs that compile the
      same programs key identically (``resize=auto`` ≡ its resolution);
    - the **environment fingerprint** covers jax, jaxlib, the backend
      platform + device kind, libtpu when present, and (CPU backend
      only) a hash of the host's CPU feature flags — a version bump or a
      different microarchitecture resolves to a *different* entry
      instead of a wrong hit.

  **verify-before-trust**: ``seal()`` (called when a run exits cleanly)
  records every cache file's sha256 in ``_sums.json`` (atomic write, the
  sink discipline). ``attach()`` re-hashes on the way in: a file whose
  recorded sum mismatches (bit rot, tampering) or that was never sealed
  (a writer died mid-run) is deleted — a clean miss XLA recompiles and
  re-stores, never a corrupt executable served.

  **warm promise**: an entry whose ``_entry.json`` manifest exists and
  whose sealed files all verify is *warm* — a joining host can check
  this before claiming (the canary gate's warm fast path,
  parallel/queue.py) and ``vft-warmup <family> ...`` populates it ahead
  of time, so join latency is a measured number (``python bench.py
  bench_coldstart``) instead of a compile stall.

Enabled by ``compile_cache=``/``compile_cache_dir=`` in all 8 configs
(``auto`` = on for TPU runs; CPU runs need an explicit dir — their
executables are microarch-scoped, and tests must stay hermetic), and
disabled wherever ``JAX_COMPILATION_CACHE_DIR`` already places JAX's
cache (:func:`env_placement` — the one placement rule, shared with
cli.py). The attach point is process-global (JAX has ONE cache directory per
process): first attach wins, multi-family runs attach one combined
entry. Hit/miss counters ride the existing ``jax.monitoring`` listeners
(telemetry/recorder.py) into every heartbeat's ``compile_cache`` section
and ``vft-fleet``. See docs/performance.md "Never compile twice, fleet
edition".
"""
from __future__ import annotations

import functools
import hashlib
import json
import os
import re
import sys
import threading
from typing import Any, Dict, List, Optional, Tuple

#: schema identifier stamped into every entry manifest; bump on breaking change
SCHEMA_VERSION = "vft.compile_cache/1"

#: per-entry metadata files (live next to JAX's own ``*-cache`` files)
MANIFEST_NAME = "_entry.json"
SUMS_NAME = "_sums.json"

#: JAX cache artifacts: ``<program>-cache`` executables (verified) and
#: ``<program>-atime`` LRU bookkeeping (ignored — mutated on every read)
_CACHE_SUFFIX = "-cache"
_ATIME_SUFFIX = "-atime"


def _safe(name: str) -> str:
    """Filesystem-safe directory component (multi-family entries embed
    comma-joined family lists)."""
    return re.sub(r"[^A-Za-z0-9._,-]+", "-", str(name))


def _sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


#: JAX's own variable: JAX reads it into ``jax_compilation_cache_dir`` at
#: import, so where it is set the cache is already placed
JAX_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def env_placement() -> Optional[str]:
    """The directory ``JAX_COMPILATION_CACHE_DIR`` names, or None.

    THE placement rule, shared with ``cli._enable_compilation_cache``:
    where the variable is set (an operator's shared mount, the chip
    driver's kept directory) it wins over ``compilation_cache_dir=``,
    ``compile_cache_dir=`` and ``VFT_COMPILE_CACHE_DIR`` — nothing in this
    repo points ``jax_compilation_cache_dir`` anywhere else, and the
    fleet store (whose per-entry directories would do exactly that)
    resolves disabled. Hit/miss counters come from ``jax.monitoring`` and
    reach ``_run.json`` either way."""
    return os.environ.get(JAX_CACHE_ENV) or None


def default_root() -> str:
    """Where executables go when nothing placed the cache: one fixed
    directory inside the checkout (config.CACHE_ROOT), the same for this
    store and for the CLI's flat per-machine cache."""
    from .config import CACHE_ROOT
    return os.environ.get("VFT_COMPILE_CACHE_DIR") or str(CACHE_ROOT / "xla")


@functools.lru_cache(maxsize=None)
def _announce_env_placement(placed: str) -> None:
    """One line per process, however many extractors ask."""
    print(f"compile cache: {JAX_CACHE_ENV}={placed} places XLA's cache; "
          "the fleet store (compile_cache=) is disabled for this process")


# -- fingerprints -------------------------------------------------------------

def _cpu_features_fingerprint() -> str:
    """Hash of this host's CPU feature flags: XLA:CPU executables bake
    them in, so they are part of the environment identity (two hosts
    with identical flag sets may share entries; different microarchs may
    not — the SIGILL hazard cli.py's per-machine cache sidestepped by
    never sharing)."""
    import platform
    flags = ""
    try:
        with open("/proc/cpuinfo", encoding="utf-8") as f:
            for line in f:
                if line.lower().startswith("flags"):
                    flags = " ".join(sorted(line.split(":", 1)[1].split()))
                    break
    except OSError:
        pass
    blob = f"{platform.machine()}|{flags}"
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def env_fingerprint(jax_version: Optional[str] = None,
                    jaxlib_version: Optional[str] = None,
                    backend: Optional[str] = None,
                    device_kind: Optional[str] = None,
                    libtpu_version: Optional[str] = None,
                    ) -> Tuple[Dict[str, Any], str]:
    """The runtime identity a compiled executable depends on, as
    ``(components dict, sha256 hex)``. Every component is overridable so
    tests can pin "what if jaxlib bumped" without installing anything —
    a changed component changes the fingerprint, which resolves to a
    different entry directory: the *miss-on-version-change* contract."""
    if jax_version is None or backend is None or device_kind is None:
        import jax
        jax_version = jax_version or jax.__version__
        if backend is None:
            backend = jax.default_backend()
        if device_kind is None:
            try:
                device_kind = jax.devices()[0].device_kind
            except Exception:
                device_kind = "?"
    if jaxlib_version is None:
        try:
            import jaxlib
            jaxlib_version = jaxlib.__version__
        except Exception:
            jaxlib_version = "?"
    if libtpu_version is None:
        try:
            from importlib import metadata
            for dist in ("libtpu", "libtpu-nightly"):
                try:
                    libtpu_version = metadata.version(dist)
                    break
                except metadata.PackageNotFoundError:
                    continue
        except Exception:
            pass
    env: Dict[str, Any] = {
        "jax": str(jax_version),
        "jaxlib": str(jaxlib_version),
        "backend": str(backend),
        "device_kind": str(device_kind),
        "libtpu": libtpu_version,
        "python": f"{sys.version_info.major}.{sys.version_info.minor}",
    }
    if env["backend"] == "cpu":
        env["cpu_features"] = _cpu_features_fingerprint()
    fp = hashlib.sha256(
        repr(sorted(env.items(), key=lambda kv: kv[0])).encode()).hexdigest()
    return env, fp


def config_fingerprint(args: Dict[str, Any],
                       resolved: Optional[Dict[str, Any]] = None) -> str:
    """cache.py's canonical resolved-config fingerprint, reused verbatim:
    the compile cache and the feature cache must agree on what
    "operationally different, semantically identical" means."""
    from .cache import config_fingerprint as _fp
    return _fp(args, resolved)


def resolved_overlay(args) -> Dict[str, Any]:
    """The ``resize=auto`` resolution predicted from the config ALONE.

    The feature cache reads the resolution off the constructed extractor
    (``resize_mode``), but the compile cache cannot wait that long: the
    expensive init-time compiles (flax ``model.init`` of a 20-iteration
    RAFT scan costs seconds) happen DURING construction, so the entry
    must be attached before it. This predictor mirrors
    ``BaseExtractor._resolve_resize_mode``'s auto rule — device for
    file-sink runs, host for print/show_pred — and is used by attach,
    warmup and the serve loop alike, so every driver computes the same
    key for the same config. (A family without a fused device resize
    resolves host internally while this predicts device; both the warmup
    and the run predict identically, so entries still line up — the only
    cost is that such a config does not share an entry with an explicit
    ``resize=host`` twin.)"""
    resolved: Dict[str, Any] = {}
    rz = args.get("resize") or "auto"
    if rz == "auto":
        save_sink = args.get("on_extraction", "print") in (
            "save_numpy", "save_pickle")
        resolved["resize"] = ("device" if save_sink
                              and not args.get("show_pred") else "host")
    ingest = args.get("ingest")
    if ingest is not None:
        resolved["ingest"] = ingest
    return resolved


def entry_key(family: str, config_fp: str, env_fp: str) -> str:
    """One sha256 over the triple: the entry directory's name."""
    return hashlib.sha256(
        f"{family}\n{config_fp}\n{env_fp}".encode()).hexdigest()


# -- the entry ---------------------------------------------------------------

class CompileCacheEntry:
    """One ``(family, config, environment)`` triple's directory of
    serialized XLA executables, with sealed-sum verification."""

    def __init__(self, root: str, family: str, config_fp: str,
                 env_fp: str, env: Optional[Dict[str, Any]] = None) -> None:
        self.root = str(root)
        self.family = str(family)
        self.config_fp = config_fp
        self.env_fp = env_fp
        self.env = dict(env or {})
        self.key = entry_key(self.family, config_fp, env_fp)
        self.dir = os.path.join(self.root, _safe(self.family),
                                self.key[:2], self.key)
        #: attach-time verdicts, published into the heartbeat section
        self.warm_at_attach = False
        self.verified = 0
        self.dropped = 0

    # -- inspection --------------------------------------------------------
    def _cache_files(self) -> List[str]:
        try:
            return sorted(n for n in os.listdir(self.dir)
                          if n.endswith(_CACHE_SUFFIX))
        except OSError:
            return []

    def _read_json(self, name: str) -> Optional[dict]:
        try:
            with open(os.path.join(self.dir, name), encoding="utf-8") as f:
                doc = json.load(f)
            return doc if isinstance(doc, dict) else None
        except (OSError, ValueError):
            return None

    def sums(self) -> Dict[str, dict]:
        doc = self._read_json(SUMS_NAME) or {}
        files = doc.get("files")
        return dict(files) if isinstance(files, dict) else {}

    def manifest(self) -> Optional[dict]:
        return self._read_json(MANIFEST_NAME)

    def is_warm(self) -> bool:
        """True when this triple carries the warm promise: a sealed
        manifest of the right schema/fingerprints whose recorded files
        all still exist on disk (verify() has already deleted any whose
        bytes rotted)."""
        man = self.manifest()
        if man is None or man.get("schema") != SCHEMA_VERSION:
            return False
        if man.get("config_fp") != self.config_fp or \
                man.get("env_fp") != self.env_fp:
            return False
        sums = self.sums()
        if not sums:
            return False
        return all(os.path.exists(os.path.join(self.dir, name))
                   for name in sums)

    # -- verify / seal ------------------------------------------------------
    def verify(self) -> Dict[str, int]:
        """Verify-before-trust, the feature cache's discipline applied to
        executables: re-hash every JAX cache file against the sealed
        sums. A mismatch (bit rot, truncation, tampering) or an unsealed
        file (its writer died before seal — completeness unprovable) is
        DELETED, so XLA sees a clean miss and recompiles, instead of
        deserializing garbage. Returns ``{"verified": n, "dropped": n}``
        and records both on the entry for the heartbeat."""
        sums = self.sums()
        verified = dropped = 0
        for name in self._cache_files():
            path = os.path.join(self.dir, name)
            rec = sums.get(name)
            ok = False
            if isinstance(rec, dict):
                try:
                    ok = _sha256_file(path) == rec.get("sha256")
                except OSError:
                    ok = False
            if ok:
                verified += 1
                continue
            reason = "sha mismatch" if rec is not None else "never sealed"
            print(f"compile cache: dropped {name} ({reason}) — a clean "
                  f"recompile replaces it ({self.dir})", file=sys.stderr)
            for victim in (path, path[:-len(_CACHE_SUFFIX)] + _ATIME_SUFFIX):
                try:
                    os.unlink(victim)
                except OSError:
                    pass
            dropped += 1
        self.verified, self.dropped = verified, dropped
        return {"verified": verified, "dropped": dropped}

    def seal(self) -> int:
        """Record the current cache files' sums + the entry manifest
        (both atomic — telemetry/jsonl.py): from here on, these
        executables are vouched for and the entry is *warm*. Called when
        a run exits; a run that dies first simply leaves unsealed files
        for the next attach to drop. Returns the sealed file count."""
        import time

        from .telemetry.jsonl import write_json_atomic
        files: Dict[str, dict] = {}
        for name in self._cache_files():
            path = os.path.join(self.dir, name)
            try:
                files[name] = {"sha256": _sha256_file(path),
                               "bytes": os.path.getsize(path)}
            except OSError:
                continue  # racing eviction: the file simply isn't sealed
        write_json_atomic(os.path.join(self.dir, SUMS_NAME),
                          {"schema": SCHEMA_VERSION, "files": files,
                           "time": round(time.time(), 3)})
        write_json_atomic(os.path.join(self.dir, MANIFEST_NAME), {
            "schema": SCHEMA_VERSION,
            "family": self.family,
            "config_fp": self.config_fp,
            "env_fp": self.env_fp,
            "env": self.env,
            "files": len(files),
            "sealed_time": round(time.time(), 3),
        })
        return len(files)

    def activate(self) -> None:
        """Point THIS process's JAX persistent compilation cache at the
        entry directory. Process-global by JAX's design — which is
        exactly why attach() is first-wins."""
        import jax
        from jax.experimental.compilation_cache import compilation_cache
        if env_placement():
            raise RuntimeError(
                f"{JAX_CACHE_ENV} is set: the cache is placed there and no "
                "entry may redirect it (resolve_root gates every attach)")
        jax.config.update("jax_compilation_cache_dir", self.dir)
        # small executables are worth caching too (cli.py's rationale)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        # JAX opens its cache at the FIRST compile: a process that compiled
        # anything before attach (extractor init work, library callers)
        # holds the old directory, or none, and would ignore the update —
        # reset so the next compile opens the entry directory
        compilation_cache.reset_cache()


# -- process-global attach ----------------------------------------------------

_lock = threading.Lock()
_active: Optional[CompileCacheEntry] = None


def resolve_root(args) -> Optional[str]:
    """The store root this run should attach to, or None (disabled).
    ``compile_cache=auto`` (the config default) is on wherever sharing
    is unconditionally safe and valuable — TPU runs — and requires an
    explicit ``compile_cache_dir`` on the CPU backend: CPU entries are
    microarch-scoped (env_fingerprint covers the flags), and tests /
    casual CPU runs must not grow a store as a side effect. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set the store is disabled, with one
    printed line (:func:`env_placement`)."""
    mode = args.get("compile_cache", "auto")
    if mode in (None, False, "", "false", "null", "off"):
        return None
    if mode not in (True, "auto", "true", "on"):
        raise ValueError(f"compile_cache={mode!r}: expected true, false "
                         "or 'auto'")
    placed = env_placement()
    if placed:
        _announce_env_placement(placed)
        return None
    explicit = args.get("compile_cache_dir")
    if mode == "auto" and explicit is None:
        import jax
        if jax.default_backend() == "cpu":
            return None
    return str(explicit) if explicit else default_root()


def _attach_entry(root: str, family: str, config_fp: str
                  ) -> CompileCacheEntry:
    """The shared attach tail: build the entry, verify-before-trust,
    record warmth, point JAX at it, publish as the process-global
    active entry (losers of the publish race return the winner)."""
    global _active
    env, env_fp = env_fingerprint()
    entry = CompileCacheEntry(root, family, config_fp, env_fp, env=env)
    with _lock:
        if _active is not None:
            return _active
        _active = entry
    from .telemetry import startup
    with startup.phase("cache_attach", family=family, entry=entry.key[:12]):
        os.makedirs(entry.dir, exist_ok=True)
        entry.verify()
        entry.warm_at_attach = entry.is_warm()
        entry.activate()
    return entry


def attach(family: str, args, resolved: Optional[Dict[str, Any]] = None
           ) -> Optional[CompileCacheEntry]:
    """Attach this process to the triple's entry: verify, activate,
    remember. First attach wins (JAX has one cache dir per process);
    later calls return the active entry unchanged. Returns None when
    ``compile_cache`` resolves disabled."""
    with _lock:
        if _active is not None:
            return _active
    root = resolve_root(args)
    if root is None:
        return None
    return _attach_entry(root, family, config_fingerprint(args, resolved))


def attach_for_args(family: str, args) -> Optional[CompileCacheEntry]:
    """Attach from a sanity-checked config, BEFORE the extractor is
    constructed — the init-time compiles (the expensive ones for the
    scan-heavy families) must already land in the entry. The resolution
    overlay is predicted from the config (:func:`resolved_overlay`)."""
    return attach(str(family), args, resolved_overlay(args))


def attach_for_extractor(ext) -> Optional[CompileCacheEntry]:
    """The lazy library-caller hook (extractors/base.py): same key as
    :func:`attach_for_args`, computed from the extractor's own args. The
    CLI/serve drivers attach earlier, pre-construction; this path only
    fires when nothing attached yet."""
    args = getattr(ext, "args", None)
    if args is None:
        return None
    return attach_for_args(str(ext.feature_type), args)


def attach_for_multi_args(per_family) -> Optional[CompileCacheEntry]:
    """Multi-family runs compile N families' programs in ONE process, so
    they attach ONE combined entry: family = the comma-joined list, the
    config fingerprint = a hash over every member family's own resolved
    fingerprint (order-insensitive). ``vft-warmup resnet,clip`` warms
    exactly this triple. ``per_family`` is the load_multi_config dict —
    callable before any extractor exists."""
    families = list(per_family)
    fps = []
    for fam in sorted(families):
        a = per_family[fam]
        fps.append(f"{fam}:{config_fingerprint(a, resolved_overlay(a))}")
    combined = hashlib.sha256("\n".join(fps).encode()).hexdigest()
    with _lock:
        if _active is not None:
            return _active
    root = resolve_root(per_family[families[0]])
    if root is None:
        return None
    return _attach_entry(root, ",".join(families), combined)


def active() -> Optional[CompileCacheEntry]:
    with _lock:
        return _active


def active_info() -> Optional[Dict[str, Any]]:
    """Compact view of the attached entry for heartbeats/reports."""
    entry = active()
    if entry is None:
        return None
    return {"family": entry.family, "entry": entry.key[:12],
            "warm_at_attach": bool(entry.warm_at_attach),
            "verified": entry.verified, "dropped": entry.dropped,
            "dir": entry.dir}


def seal_active() -> int:
    """Seal the attached entry (run exit). Returns sealed file count;
    0 when nothing is attached. Never raises into the caller's finally —
    an unsealed entry only costs the next host a recompile."""
    entry = active()
    if entry is None:
        return 0
    try:
        return entry.seal()
    except Exception as e:
        print(f"compile cache: seal failed ({type(e).__name__}: {e}) — "
              f"entry stays cold, next attach recompiles", file=sys.stderr)
        return 0


def detach_for_tests() -> None:
    """Drop the process-global attach so tests can re-attach. Leaves
    jax's cache dir pointing wherever it was (tests restore it)."""
    global _active
    with _lock:
        _active = None


# -- ahead-of-time warmup (vft-warmup) ----------------------------------------

def _synth_clip(path: str, frames: int = 48, w: int = 320,
                h: int = 240, fps: float = 19.62) -> str:
    """A small synthetic clip with natural-ish low-frequency content
    (the tests' stand-in recipe) so warmup needs no corpus. Shapes are
    what compile keys on, not pixels — but pass a representative video
    (``video_paths=``) when source resolution feeds a device-resize
    program you want warm."""
    import cv2
    import numpy as np
    wtr = cv2.VideoWriter(path, cv2.VideoWriter_fourcc(*"mp4v"),
                          fps, (w, h))
    if not wtr.isOpened():
        raise RuntimeError("cv2 cannot encode the synthetic warmup clip; "
                           "pass video_paths=<clip> instead")
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    for t in range(frames):
        frame = np.stack([
            127 + 120 * np.sin(xx / 40 + t / 9),
            127 + 120 * np.sin(yy / 30 - t / 13),
            127 + 120 * np.sin((xx + yy) / 50 + t / 7),
        ], axis=-1)
        wtr.write(frame.clip(0, 255).astype(np.uint8))
    wtr.release()
    return path


def _warmup_one(family: str, overrides: Dict[str, Any]) -> Dict[str, Any]:
    """Warm ONE family's triple in THIS process: construct the real
    extractor under the real (sanity-checked) config, run one throwaway
    extraction so every first-video program compiles into the entry,
    seal. The warmup subprocesses vft-warmup spawns call this; tests may
    call it directly."""
    import contextlib
    import tempfile
    import time

    from .config import load_config, sanity_check
    from .registry import get_extractor_cls
    from .telemetry.recorder import compile_cache_baseline, \
        compile_cache_summary

    overrides = dict(overrides or {})
    # the warmup run itself is throwaway; its CONFIG must still resolve
    # like production (a save sink resolves resize=auto -> device, the
    # shape production file-sink runs compile)
    overrides.setdefault("on_extraction", "save_numpy")
    # the entry point exists to populate the store: an absent/auto key
    # attaches even on CPU (the operator asked for warmth explicitly)
    if overrides.get("compile_cache") in (None, "auto"):
        overrides["compile_cache"] = True
    video = overrides.pop("video_paths", None)
    if isinstance(video, (list, tuple)):
        video = video[0] if video else None
    with tempfile.TemporaryDirectory(prefix="vft_warmup_") as td:
        if video is None:
            video = _synth_clip(os.path.join(td, "warmup.mp4"))
        overrides["video_paths"] = [str(video)]
        overrides["output_path"] = os.path.join(td, "out")
        overrides["tmp_path"] = os.path.join(td, "tmp")
        cfg = load_config(family, overrides)
        sanity_check(cfg)
        baseline = compile_cache_baseline()
        t0 = time.perf_counter()
        # attach BEFORE construction: the init-time compiles are part of
        # the warm set (the same order the CLI driver uses)
        entry = attach_for_args(family, cfg)
        if entry is None:
            return {"family": family, "status": "disabled",
                    "note": "compile_cache resolved disabled "
                            f"(compile_cache=false, or {JAX_CACHE_ENV} "
                            "is set)"}
        warm_before = entry.warm_at_attach
        ext = get_extractor_cls(family)(cfg)
        with contextlib.redirect_stdout(sys.stderr):
            ext._extract(str(video))
        sealed = entry.seal()
        summary = compile_cache_summary(baseline)
        return {"family": family, "status": "ok", "entry": entry.key[:12],
                "dir": entry.dir, "warm_before": bool(warm_before),
                "compiled": int(summary.get("misses", 0)),
                "reused": int(summary.get("hits", 0)),
                "sealed_files": sealed,
                "seconds": round(time.perf_counter() - t0, 2)}


def warmup_main(argv: Optional[List[str]] = None) -> None:
    """``vft-warmup <family>[,<family>...] ... [key=value ...]``: compile
    every listed family's programs into the shared store ahead of time,
    one fresh subprocess per family (JAX holds one cache dir per
    process, and a cold subprocess is exactly the joining-host shape the
    warmth is for). Multi-family triples (``resnet,clip``) warm as one
    combined entry — the same entry a ``feature_type=resnet,clip`` run
    attaches."""
    argv = list(sys.argv[1:] if argv is None else argv)
    families: List[str] = []
    overrides: List[str] = []
    for a in argv:
        (overrides if "=" in a else families).append(a)
    if not families:
        raise SystemExit(
            "Usage: vft-warmup <family>[,<family>...] ... [key=value ...]\n"
            "e.g.   vft-warmup resnet clip compile_cache_dir=/srv/vft/cc\n"
            "(docs/performance.md 'Never compile twice, fleet edition')")
    from .config import parse_dotlist
    from .registry import parse_feature_types
    over = parse_dotlist(overrides)
    failures = 0
    for spec in families:
        fams = parse_feature_types(spec)  # validates names
        if len(fams) > 1:
            # combined triple: warmed by a real multi-family CLI run in
            # the subprocess (attach_for_multi keys it)
            result = _spawn_warmup_multi(spec, over)
        else:
            result = _spawn_warmup(fams[0], over)
        if result.get("status") == "ok":
            tag = "warm already, re-verified" if result.get("warm_before") \
                else f"compiled {result.get('compiled', '?')} program(s)"
            print(f"vft-warmup: {spec}: {tag} in "
                  f"{result.get('seconds', '?')}s -> entry "
                  f"{result.get('entry')} ({result.get('sealed_files')} "
                  f"sealed file(s), {result.get('dir')})")
        else:
            failures += 1
            print(f"vft-warmup: {spec}: FAILED — "
                  f"{result.get('note') or result.get('error')}",
                  file=sys.stderr)
    if failures:
        raise SystemExit(1)


_WARMUP_WORKER = """\
import json, sys
result = {}
try:
    from video_features_tpu.compile_cache import _warmup_one
    result = _warmup_one(sys.argv[1], json.loads(sys.argv[2]))
except BaseException as e:
    result = {"family": sys.argv[1], "status": "error",
              "error": f"{type(e).__name__}: {e}"}
print("VFT_WARMUP_RESULT " + json.dumps(result))
"""

_WARMUP_MULTI_WORKER = """\
import contextlib, json, os, sys, tempfile, time
result = {}
try:
    from video_features_tpu import compile_cache
    from video_features_tpu.cli import main as cli_main
    spec, over = sys.argv[1], json.loads(sys.argv[2])
    if over.get("compile_cache") in (None, "auto"):
        over["compile_cache"] = True
    over.setdefault("on_extraction", "save_numpy")
    video = over.pop("video_paths", None)
    if isinstance(video, list):
        video = video[0] if video else None
    with tempfile.TemporaryDirectory(prefix="vft_warmup_") as td:
        if video is None:
            video = compile_cache._synth_clip(os.path.join(td, "w.mp4"))
        argv = [f"feature_type={spec}", f"output_path={td}/out",
                f"tmp_path={td}/tmp", f"video_paths=[{video}]"]
        argv += [f"{k}={json.dumps(v) if isinstance(v, (bool, type(None))) else v}"
                 for k, v in over.items()]
        t0 = time.perf_counter()
        with contextlib.redirect_stdout(sys.stderr):
            cli_main(argv)
        entry = compile_cache.active()
        if entry is None:
            result = {"family": spec, "status": "disabled",
                      "note": "compile_cache resolved disabled"}
        else:
            result = {"family": spec, "status": "ok",
                      "entry": entry.key[:12], "dir": entry.dir,
                      "warm_before": bool(entry.warm_at_attach),
                      "compiled": None, "sealed_files": entry.seal(),
                      "seconds": round(time.perf_counter() - t0, 2)}
except BaseException as e:
    result = {"family": sys.argv[1], "status": "error",
              "error": f"{type(e).__name__}: {e}"}
print("VFT_WARMUP_RESULT " + json.dumps(result))
"""


def _run_warmup_worker(code: str, spec: str, over) -> Dict[str, Any]:
    import subprocess

    from .config import _plain
    proc = subprocess.run(
        [sys.executable, "-c", code, spec, json.dumps(_plain(dict(over)))],
        capture_output=True, text=True)
    for line in reversed((proc.stdout or "").splitlines()):
        if line.startswith("VFT_WARMUP_RESULT "):
            try:
                return json.loads(line[len("VFT_WARMUP_RESULT "):])
            except ValueError:
                break
    tail = (proc.stderr or proc.stdout or "")[-800:]
    return {"family": spec, "status": "error",
            "error": f"warmup subprocess rc={proc.returncode}: {tail}"}


def _spawn_warmup(family: str, over) -> Dict[str, Any]:
    return _run_warmup_worker(_WARMUP_WORKER, family, over)


def _spawn_warmup_multi(spec: str, over) -> Dict[str, Any]:
    return _run_warmup_worker(_WARMUP_MULTI_WORKER, spec, over)


if __name__ == "__main__":
    warmup_main()
