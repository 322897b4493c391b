"""Content-addressed feature cache: never decode (or compute) twice.

At millions-of-users scale repeat content is the dominant pattern
(ROADMAP item 1): the same trailer, meme clip or re-uploaded video
arrives byte-identical thousands of times, and the cold CLI re-pays the
full decode -> transform -> device -> sink cost for every copy. With
``cache=true`` a finished extraction is stored once under a key that
captures everything that could change its value, and every later
request for the same (content, configuration, weights) triple is served
from the store without constructing a decoder at all:

  **content identity** — ``sha256`` of the input file's bytes (streamed,
  memoized per ``(path, size, mtime)`` so a corpus pass hashes each file
  once). Sources that cannot be byte-hashed (pipes, devices) fall back
  to the decode-plan identity: the probed stream properties plus the
  exact ``plan_frame_selection`` mapping the extraction would use — the
  same walk ``VideoSource`` and the shared-decode ``FrameBus`` agree on,
  so two sources that would decode identical frame streams key alike.

  **config fingerprint** — the sanity-checked config with every
  non-semantic key dropped (paths, worker counts, telemetry switches,
  retry policy: none of them change a feature value) and every
  value-bearing default RESOLVED: the extractor's own ``resize_mode`` /
  ``ingest`` resolutions replace the raw ``resize=auto`` / ``ingest=null``
  strings, so ``resize=auto`` and an explicit ``resize=device`` hash
  identically whenever they resolve the same (pinned by
  tests/test_cache.py).

  **weights fingerprint** — sha256 of every checkpoint file the
  extractor's ``weights/store.resolve_params`` actually loaded (captured
  at init via :func:`~.weights.store.start_weights_capture`), so a
  re-converted or fine-tuned checkpoint can never serve stale features.
  ``allow_random_weights`` runs key under a ``random:`` sentinel — the
  seeded init is deterministic, which is what the tests and benches rely
  on.

Serving is **verify-before-trust**: a stored entry carries the PR-5
quantization-tolerant content signature (telemetry/health.py) of every
feature tensor, recomputed on load; a mismatch (bit rot, torn write,
tampering) deletes the entry and reports a miss instead of serving bad
features. Writes go through the same atomic temp+fsync+rename
discipline as the sinks (utils/sinks.py ``_write_bytes_atomic``), so a
preempted worker can never leave a half-written entry that later
lookups would trust.

Telemetry: ``vft_cache_{hit,miss,bypass}_total{family=...}`` counters
(bypass = work avoided by the filename skip-if-exists check WITHOUT
consulting the cache — docs/performance.md documents the precedence:
cache hit > filename skip), a ``cache`` section in every heartbeat
(telemetry/recorder.py ``cache_snapshot``), and ``cache.lookup`` /
``cache.hit`` / ``cache.store`` timeline spans when ``trace=true``.
"""
from __future__ import annotations

import hashlib
import os
import pickle
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

#: schema identifier stamped into every entry; bump on breaking change
SCHEMA_VERSION = "vft.feature_cache/1"

#: config keys that can never change a feature VALUE — dropped from the
#: fingerprint so runs that differ only operationally share entries.
#: (feature_type/model_name stay IN: they select the network.)
NON_SEMANTIC_KEYS = frozenset({
    # where things land / come from
    "output_path", "tmp_path", "keep_tmp_files",
    "video_paths", "file_with_video_paths", "config",
    # how work is scheduled, observed and retried
    "video_workers", "decode_workers", "decode_depth", "video_decode",
    "fanout_depth", "cross_video_batching", "clip_batch_size",
    "batch_size", "flow_stack_batch", "model_parallel",
    "mesh_devices", "distributed",
    "telemetry", "metrics_interval_s", "trace", "health", "parity",
    "roofline", "history", "alerts",
    "profile", "profile_trace_dir", "compilation_cache_dir",
    "retry_attempts", "retry_backoff_s", "video_deadline_s",
    "retry_failed",
    # fleet scheduling (parallel/queue.py) moves work between hosts; it
    # cannot change what any (video, config, weights) triple computes
    "fleet", "fleet_lease_s", "fleet_max_reclaims", "fleet_canary",
    # the cache's own knobs must not key the cache; the compile cache's
    # knobs (compile_cache.py) likewise change where executables come
    # from, never what any program computes. cache_scope changes WHO may
    # observe an entry (a tenant salt in the key, below), never the
    # feature values — it must not perturb the config fingerprint
    "cache", "cache_dir", "cache_scope",
    "compile_cache", "compile_cache_dir",
    # chaos-injection plans perturb scheduling/IO, never feature values
    # (a fault either recovers bit-identically or fails the video)
    "inject",
    # serve-mode knobs (serve.py): spool plumbing, not feature values
    "spool_dir", "serve_max_pending", "serve_poll_interval_s",
    "serve_idle_exit_s", "serve_max_requests", "serve_workers",
    "serve_warmup_video", "serve_slo_s",
    # gateway knobs (gateway.py): ingress admission/deadline plumbing
    "gateway_tenants", "gateway_port", "gateway_host",
    "gateway_max_queued", "gateway_spool_bound", "gateway_max_body_mb",
    "gateway_poll_interval_s", "gateway_expire_grace_s",
    "gateway_default_timeout_s",
    # sink format changes the FILE, not the feature values; entries store
    # arrays and are written through whichever sink the run uses
    "on_extraction", "show_pred",
    # storage lifecycle knobs (gc.py): eviction is always a recoverable
    # miss — deleting an entry can change how long a run takes, never
    # what any (video, config, weights) triple computes
    "gc", "gc_quota_gb", "gc_cache_retention_s",
    "gc_compile_retention_s", "gc_spool_retention_s",
    "gc_inbox_retention_s", "gc_incident_retention_s",
    "gc_quarantine_retention_s", "gc_staging_retention_s",
    "gc_interval_s",
})

#: config keys that DO bear on feature values — they stay in the
#: fingerprint, and the choice is now explicit: ``vft-lint`` rule VFT001
#: fails the build when a key in any family YAML (or read by a
#: validator) is in neither set, which is exactly how every one of
#: PRs 9/11/13/14 almost re-introduced the cache-poisoning hazard this
#: pair of sets exists to prevent. When adding a config key, ask "can
#: two runs that differ only in this key produce different features?" —
#: yes -> here, no -> NON_SEMANTIC_KEYS above.
SEMANTIC_KEYS = frozenset({
    # what network, on which backend, at what precision
    "feature_type", "model_name", "device", "precision",
    "weights_path", "allow_random_weights",
    # which frames reach it
    "extraction_fps", "extraction_total", "fps_mode",
    # how pixels are prepared (resolved resize/ingest overlay included)
    "resize", "ingest", "side_size", "resize_to_smaller_edge",
    # clip windowing (value-bearing: changes the stacks the net sees)
    "stack_size", "step_size", "streams",
    # flow-family knobs (iteration counts and flow nets change outputs)
    "flow_type", "flow_iters", "flow_weights_path",
    "flow_model_weights_path", "iters", "finetuned_on",
    # the token family: the published architecture it runs, this chip's
    # share of a layer (which experts add to the result), and the bound on
    # a row's segments (max_segments only where a row would overflow it,
    # but then it moves a window into another row's rounding)
    "architecture", "layer_shards", "layer_shard_rank", "max_segments",
    # kernel dispatch (implementations are near- but not bit-identical)
    "vision_attn",
    # CLIP text side + prediction rendering inputs
    "bpe_path", "pred_texts",
    # VGGish post-processing
    "frontend", "postprocess", "pca_weights_path",
})

_sha_lock = threading.Lock()
#: (abspath, size, mtime_ns) -> hex digest; bounded FIFO
_sha_memo: Dict[tuple, str] = {}
_SHA_MEMO_CAP = 4096


def file_sha256(path: str) -> str:
    """Streamed sha256 of a file, memoized on ``(path, size, mtime)`` so
    a two-pass corpus run hashes each input once (the memo is the cheap
    in-process analog of the content-addressed store itself)."""
    st = os.stat(path)
    key = (os.path.abspath(path), st.st_size, st.st_mtime_ns)
    with _sha_lock:
        hit = _sha_memo.get(key)
    if hit is not None:
        return hit
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    digest = h.hexdigest()
    with _sha_lock:
        if len(_sha_memo) >= _SHA_MEMO_CAP:
            _sha_memo.pop(next(iter(_sha_memo)), None)
        _sha_memo[key] = digest
    return digest


def plan_identity(video_path: str, fps: Optional[float],
                  total: Optional[int]) -> str:
    """Decode-plan-level identity for sources that cannot be byte-hashed:
    the probed stream properties plus the exact frame-selection mapping
    (utils/io.py ``plan_frame_selection`` — the walk every decoded-stream
    consumer agrees on). Weaker than a byte hash (two different encodes
    with identical probe properties would collide), so it is only the
    FALLBACK identity; the sha256 fast path wins whenever the bytes are
    readable."""
    from .utils.io import get_video_props, plan_frame_selection
    props = get_video_props(video_path)
    out_fps, index_map, num_frames = plan_frame_selection(
        props["fps"], props["num_frames"], fps=fps, total=total)
    h = hashlib.sha256()
    h.update(repr((os.path.basename(str(video_path)),
                   round(float(props["fps"]), 4),
                   int(props["num_frames"]),
                   int(props["width"]), int(props["height"]),
                   round(float(out_fps), 4), int(num_frames))).encode())
    if index_map is not None:
        h.update(np.asarray(index_map, np.int64).tobytes())
    return "plan:" + h.hexdigest()


def content_identity(video_path: str, fps: Optional[float] = None,
                     total: Optional[int] = None) -> str:
    """``sha256:<hex>`` of the file bytes (fast path), or the
    ``plan:<hex>`` decode-plan identity when the bytes are unreadable."""
    try:
        return "sha256:" + file_sha256(str(video_path))
    except OSError:
        return plan_identity(video_path, fps, total)


def canonical_config(args: Dict[str, Any],
                     resolved: Optional[Dict[str, Any]] = None
                     ) -> Dict[str, Any]:
    """The value-bearing view of a sanity-checked config: non-semantic
    keys dropped, ``resolved`` overlays (the extractor's own
    ``resize_mode``/``ingest`` resolutions) replacing their raw keys,
    and nested dicts flattened deterministically."""
    from .config import _plain
    plain = _plain(dict(args))
    out = {k: v for k, v in plain.items() if k not in NON_SEMANTIC_KEYS}
    for k, v in (resolved or {}).items():
        if v is not None:
            out[k] = v
    return out


def config_fingerprint(args: Dict[str, Any],
                       resolved: Optional[Dict[str, Any]] = None) -> str:
    """sha256 over the sorted canonical config repr — two configs that
    resolve to the same extraction semantics fingerprint identically."""
    canon = canonical_config(args, resolved)
    blob = repr(sorted(canon.items(), key=lambda kv: kv[0]))
    return hashlib.sha256(blob.encode()).hexdigest()


def weights_fingerprint(capture: Optional[List[dict]]) -> str:
    """sha256 over the (sorted) identities of every checkpoint the
    extractor resolved: ``{model_key, sha256}`` per resolution, or the
    ``random:{model_key}`` sentinel for seeded random init. An empty /
    missing capture (extractor resolved nothing — unlikely but legal)
    keys as ``'none'``."""
    if not capture:
        return "none"
    items = []
    for rec in capture:
        if rec.get("random"):
            items.append(f"random:{rec.get('model_key')}")
        else:
            items.append(f"{rec.get('model_key')}:{rec.get('sha256')}")
    blob = "\n".join(sorted(items))
    return hashlib.sha256(blob.encode()).hexdigest()


def entry_key(content_id: str, config_fp: str, weights_fp: str,
              tenant: Optional[str] = None) -> str:
    """The store key: one sha256 over the three identity components —
    plus, under ``cache_scope=tenant``, the requesting tenant's id as a
    fourth component, so one tenant's entries can never be observed by
    (or served to) another. The default ``shared`` scope omits it: at
    fleet scale cross-tenant dedup of repeat content is the dominant
    win, and byte-identical inputs hash to one entry for everyone."""
    salt = f"\ntenant:{tenant}" if tenant else ""
    return hashlib.sha256(
        f"{content_id}\n{config_fp}\n{weights_fp}{salt}".encode()
    ).hexdigest()


def default_cache_dir() -> str:
    return os.environ.get(
        "VFT_CACHE_DIR",
        os.path.join(os.path.expanduser("~"), ".cache",
                     "video_features_tpu", "feature_cache"))


class FeatureCache:
    """One extractor's handle on the content-addressed store.

    Entries live at ``{root}/{family}/{key[:2]}/{key}.pkl`` (two-level
    fan-out keeps directories small at corpus scale). The handle is
    cheap; all state is the filesystem plus the weights/config
    fingerprints computed once at attach time.
    """

    def __init__(self, root: str, family: str, config_fp: str,
                 weights_fp: str, *, fps: Optional[float] = None,
                 total: Optional[int] = None,
                 scope: str = "shared") -> None:
        self.root = str(root)
        self.family = str(family)
        self.config_fp = config_fp
        self.weights_fp = weights_fp
        self.scope = str(scope)
        self._fps = fps
        self._total = total

    # -- construction ------------------------------------------------------
    @classmethod
    def for_extractor(cls, ext) -> Optional["FeatureCache"]:
        """Build the handle from a constructed extractor, or None when
        ``cache=false``. Resolution happens HERE, after subclass init:
        the extractor's ``resize_mode``/``ingest`` attributes are the
        ground truth the raw ``resize=auto``/``ingest=null`` strings
        resolve to, which is what makes ``resize=auto`` and its resolved
        value share entries."""
        args = getattr(ext, "args", None)
        if args is None or not bool(args.get("cache", False)):
            return None
        root = args.get("cache_dir") or default_cache_dir()
        resolved = {}
        for attr, key in (("resize_mode", "resize"), ("ingest", "ingest")):
            val = getattr(ext, attr, None)
            if val is not None:
                resolved[key] = val
        config_fp = config_fingerprint(args, resolved)
        weights_fp = weights_fingerprint(
            getattr(ext, "_weights_capture", None))
        return cls(os.path.join(root, str(ext.feature_type)),
                   ext.feature_type, config_fp, weights_fp,
                   fps=args.get("extraction_fps"),
                   total=args.get("extraction_total"),
                   scope=args.get("cache_scope", "shared") or "shared")

    # -- keying ------------------------------------------------------------
    def key_for(self, video_path: str) -> str:
        cid = content_identity(video_path, self._fps, self._total)
        if self.scope == "tenant":
            # isolation semantics (docs/serving.md): the requesting
            # tenant (thread-local, minted into the request id by the
            # gateway) salts the key, so a hit can only ever be served
            # to the tenant whose extraction stored it. Untenanted work
            # (batch CLI, spool-direct) keys under its own sentinel.
            from .telemetry.context import current_tenant
            return entry_key(cid, self.config_fp, self.weights_fp,
                             tenant=current_tenant() or "_untenanted")
        return entry_key(cid, self.config_fp, self.weights_fp)

    def entry_path(self, key: str) -> str:
        return os.path.join(self.root, key[:2], key + ".pkl")

    # -- lookup / store ----------------------------------------------------
    def lookup(self, video_path: str,
               expected_keys: Optional[Sequence[str]] = None
               ) -> Optional[Dict[str, np.ndarray]]:
        """The stored features for ``video_path`` under this cache's
        fingerprints, or None (miss). A hit is re-verified against the
        stored quantization-tolerant signatures (telemetry/health.py)
        before being served; an entry that fails to load, fails the
        schema/keys check or fails signature verification is deleted and
        reported as a miss — corrupted bytes are never served."""
        from .telemetry import trace
        from .telemetry.health import content_signature
        from .utils import inject

        with trace.span("cache.lookup", video=str(video_path),
                        family=self.family):
            key = self.key_for(video_path)
            path = self.entry_path(key)
            if not os.path.exists(path):
                return None
            try:
                fault = inject.fire("cache.lookup", video=str(video_path),
                                    key=key[:12])
                if fault is not None and fault.kind == "torn":
                    # bit rot / a torn pre-atomic-writer entry: truncate
                    # the stored bytes so verify-before-trust must catch it
                    with open(path, "r+b") as f:
                        f.truncate(max(1, os.path.getsize(path) // 2))
                with open(path, "rb") as f:
                    entry = pickle.load(f)
                feats = entry["feats"]
                sigs = entry["sigs"]
                if entry.get("schema") != SCHEMA_VERSION:
                    raise ValueError(
                        f"schema {entry.get('schema')!r} != {SCHEMA_VERSION}")
                if expected_keys is not None and \
                        set(feats) != set(expected_keys):
                    raise ValueError(
                        f"entry keys {sorted(feats)} != expected "
                        f"{sorted(expected_keys)}")
                for k, arr in feats.items():
                    got = content_signature(np.asarray(arr))
                    if got != sigs.get(k):
                        raise ValueError(
                            f"content signature mismatch for key {k!r}")
            except Exception as e:
                # torn write / bit rot / stale schema: drop the entry so
                # the recompute below repopulates it, and never serve it
                print(f"cache: dropping corrupted entry {path} "
                      f"({type(e).__name__}: {e}) — treating as a miss")
                try:
                    os.unlink(path)
                except OSError:
                    pass
                return None
            try:
                # last-hit signal for the LRU eviction plane (gc.py):
                # mtime bump on a VERIFIED hit only — no sidecar file, so
                # gc=false runs stay byte-identical in artifacts
                os.utime(path)
            except OSError:
                pass
            trace.instant("cache.hit", video=str(video_path),
                          family=self.family, key=key[:12])
            return feats

    def store(self, video_path: str, feats: Dict[str, Any]) -> str:
        """Write one entry atomically (temp + fsync + rename, the sink
        discipline) with per-key content signatures; returns the key."""
        from .telemetry import trace
        from .telemetry.health import content_signature
        from .utils import inject
        from .utils.sinks import _write_bytes_atomic

        with trace.span("cache.store", video=str(video_path),
                        family=self.family):
            inject.fire("cache.store", video=str(video_path),
                        family=self.family)
            key = self.key_for(video_path)
            arrays = {k: np.asarray(v) for k, v in feats.items()}
            entry = {
                "schema": SCHEMA_VERSION,
                "family": self.family,
                "video": os.path.basename(str(video_path)),
                "config_fp": self.config_fp,
                "weights_fp": self.weights_fp,
                "sigs": {k: content_signature(a)
                         for k, a in arrays.items()},
                "feats": arrays,
                "time": round(time.time(), 3),
            }
            _write_bytes_atomic(self.entry_path(key), pickle.dumps(entry))
            return key


# -- store maintenance -------------------------------------------------------

def cache_stats(root: Optional[str] = None) -> Dict[str, Any]:
    """Entry count + byte total per family under ``root`` (operator
    visibility; the serve heartbeat's counters are the live view)."""
    root = root or default_cache_dir()
    out: Dict[str, Any] = {"root": root, "families": {}, "entries": 0,
                           "bytes": 0}
    if not os.path.isdir(root):
        return out
    for family in sorted(os.listdir(root)):
        fam_dir = os.path.join(root, family)
        if not os.path.isdir(fam_dir):
            continue
        n = b = 0
        for dirpath, _dirnames, filenames in os.walk(fam_dir):
            for fn in filenames:
                if fn.endswith(".pkl"):
                    n += 1
                    try:
                        b += os.path.getsize(os.path.join(dirpath, fn))
                    except OSError:
                        pass
        out["families"][family] = {"entries": n, "bytes": b}
        out["entries"] += n
        out["bytes"] += b
    return out
