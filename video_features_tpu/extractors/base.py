"""Extraction lifecycle shared by all families.

Mirrors reference models/_base/base_extractor.py:11-127:
``_extract`` = skip-if-exists -> ``extract`` -> sink dispatch, with per-video
error isolation handled by the caller via ``utils.sinks.safe_extract``.
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional

import numpy as np

from ..config import Config
from ..telemetry import startup as _startup
from ..utils import sinks


class BaseExtractor:
    output_feat_keys: List[str]

    def __init__(self, args: Config) -> None:
        self.feature_type = args.feature_type
        self.on_extraction = args.get("on_extraction", "print")
        self.tmp_path = str(args.tmp_path)
        self.output_path = str(args.output_path)
        self.keep_tmp_files = bool(args.get("keep_tmp_files", False))
        self.device = args.get("device", "auto")
        self.precision = args.get("precision", "float32")
        import jax
        # before the first program: every compile or load of this process
        # is on the start-up ledger, for a library caller too
        _startup.install()
        if self.device == "cpu":
            # device=cpu must not claim a chip on a TPU host
            jax.config.update("jax_platforms", "cpu")
        # the first touch of the backend (the TPU runtime starts here)
        with _startup.phase("backend", device=self.device):
            backend = jax.default_backend()
        if self.device == "tpu" and backend != "tpu":
            # when libtpu fails to start JAX falls back to the CPU with a
            # warning; a run that asked for the chip must not carry on there
            raise RuntimeError(
                f"device=tpu, but JAX's default backend is "
                f"{backend!r} (devices: {jax.devices()}; "
                f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). Is "
                "another process holding the chip? Pass device=cpu to run "
                "on the CPU on purpose.")
        if self.precision == "float32":
            # full-fp32 accumulation for parity with the torch reference;
            # 'bfloat16' mode keeps the MXU-native fast path instead
            jax.config.update("jax_default_matmul_precision", "highest")
        self.show_pred = bool(args.get("show_pred", False))
        # health=true (telemetry/health.py): digest every feature tensor
        # at the sink boundary into {output_path}/_health.jsonl and refuse
        # to write NaN/Inf (routed through the faults taxonomy as POISON).
        # Off by default; the disabled cost is this one attribute read.
        self.health = bool(args.get("health", False))
        # parity=true (telemetry/parity.py): per-seam numerics digests
        # (decode -> transform -> backbone -> head) into
        # {output_path}/_parity.jsonl. Off by default; taps are only
        # installed when this attribute is set, so the off path is
        # byte-identical (no transform wrapper, no per-batch branch
        # beyond this one attribute read).
        self.parity = bool(args.get("parity", False))
        # cache=true (cache.py): content-addressed feature cache keyed on
        # (input sha256, resolved-config fingerprint, weights sha). The
        # weights capture must start BEFORE the subclass __init__ resolves
        # its params (weights/store.py resolve_params records what it
        # loaded into this list); the FeatureCache handle itself is built
        # lazily on first _extract, after every resolved attribute
        # (resize_mode, ingest) exists.
        self.cache_enabled = bool(args.get("cache", False))
        if self.cache_enabled:
            from ..weights import store as _wstore
            self._weights_capture = _wstore.start_weights_capture()
        self._cache = None
        self._cache_built = False
        # compile_cache= (compile_cache.py): the fleet-shared persistent
        # XLA store. The CLI/serve drivers attach explicitly right after
        # construction; this lazy flag covers library callers who invoke
        # _extract directly (attach is process-global first-wins, so the
        # double path cannot double-attach).
        self._compile_cache_checked = False
        # roofline= (telemetry/roofline.py): same lazy library-caller
        # coverage — the CLI starts the observer itself; a direct
        # _extract caller gets one homed on output_path, closed (and
        # _roofline.json written) at interpreter exit
        self._roofline_checked = False
        # video_decode=process: each video's decode+transform runs in a
        # spawned worker process (utils/io.py ProcessVideoSource) — lifts
        # the parent-GIL ceiling on numpy/PIL transform work on multi-core
        # hosts. Default 'inline' (decode on the calling/video_workers
        # thread).
        self.video_decode = args.get("video_decode") or "inline"
        if self.video_decode not in ("inline", "process", "parallel"):
            raise NotImplementedError(
                f"video_decode={self.video_decode!r}: expected 'inline', "
                "'process' or 'parallel'")
        # decode_workers: intra-video parallel decode width for
        # video_decode=parallel (utils/io.py ParallelVideoSource)
        raw_dw = args.get("decode_workers")
        self.decode_workers = 2 if raw_dw is None else int(raw_dw)
        if self.decode_workers < 1:
            raise ValueError(
                f"decode_workers={self.decode_workers}: need >= 1")
        # decode_depth: per-worker frame-queue cap (None -> full segment
        # for transformed streams, 64 for raw-frame streams)
        raw_dd = args.get("decode_depth")
        self.decode_depth = None if raw_dd is None else int(raw_dd)
        self.args = args

    def video_source(self, video_path: str, **kwargs):
        """Family-agnostic VideoSource factory honoring video_decode and
        fps_mode (``reencode`` = the reference's lossy temp-file decode
        path for golden/parity runs, utils/io.py module docstring).

        Fault-tolerance hooks (utils/faults.py): when a FaultContext is
        active on this thread, its ``decode_override`` (the degradation
        ladder's demoted mode for a retry) replaces ``video_decode``, and
        the constructed source is registered so the per-video deadline
        watchdog can kill its in-flight decode.

        Shared-decode hook (parallel/fanout.py): inside a multi-family
        run a SharedDecodeSession is installed on this thread; the first
        attempt subscribes to the video's single shared decode pass and
        gets a SharedFrameSource with the same observable surface. A
        declined subscription (retry attempt, unsupported knob) falls
        through to a private source below — isolation over sharing."""
        from ..parallel import fanout
        from ..utils import faults
        if self.parity:
            # parity taps the decode and transform seams by wrapping the
            # host transform BEFORE the shared-decode subscribe, so the
            # shared and private paths digest the same tensors on this
            # family's own thread. Only installed when parity=true: a
            # wrapper is never None, and utils/io.py sizes parallel
            # decode queues on `transform is not None` — the off path
            # must stay byte-identical.
            from ..telemetry import parity as _parity
            kwargs["transform"] = _parity.TransformTap(
                kwargs.get("transform"), str(video_path), self.feature_type)
        session = fanout.current_session()
        if session is not None:
            sub = session.subscribe(self.feature_type, **kwargs)
            if sub is not None:
                # the bus registered it with the fault context already
                # (before its arrival barrier, so the watchdog can cancel
                # a family stuck waiting for its siblings)
                from .. import telemetry
                if telemetry.current_span() is not None:
                    telemetry.annotate(video_fps=sub.fps,
                                       video_frames=len(sub))
                    telemetry.event("source", mode="shared",
                                    cls=type(sub).__name__)
                return sub
        from ..utils.io import (ParallelVideoSource, ProcessVideoSource,
                                VideoSource)
        ctx = faults.current_context()
        mode = self.video_decode
        if ctx is not None and ctx.decode_override:
            mode = ctx.decode_override
        cls = {"process": ProcessVideoSource,
               "parallel": ParallelVideoSource}.get(mode, VideoSource)
        if cls is ParallelVideoSource:
            kwargs.setdefault("decode_workers", self.decode_workers)
            if self.decode_depth is not None:
                kwargs.setdefault("depth", self.decode_depth)
        if self.args.get("fps_mode", "select") == "reencode":
            kwargs.setdefault("fps_mode", "reencode")
            kwargs.setdefault("tmp_path", self.args.get("tmp_path", "tmp"))
            kwargs.setdefault("keep_tmp", self.keep_tmp_files)
        from ..telemetry import trace as _trace
        # probing can be slow (container metadata recount, reencode temp
        # file, worker spawn): give it its own timeline span (no-op when
        # trace=false)
        with _trace.span("source_probe", video=str(video_path), mode=mode):
            src = cls(video_path, **kwargs)
        if ctx is not None:
            ctx.register(src)
        # telemetry (no-ops without an active span): the source's probed
        # properties give the span its fps/frame-count fields, and the
        # event records which decode class actually served each attempt
        # (the ladder may have demoted it)
        from .. import telemetry
        if telemetry.current_span() is not None:
            try:
                n_frames = len(src)
            except Exception:
                n_frames = None
            telemetry.annotate(video_fps=getattr(src, "fps", None),
                               video_frames=n_frames)
            telemetry.event("source", mode=mode, cls=type(src).__name__)
        return src

    def _data_mesh(self):
        """Device mesh for this extractor's runners.

        ``mesh_devices`` (config) pins the width explicitly — how tests and
        the driver dryrun shard real extractors over the virtual CPU mesh.
        Default: all local devices on TPU; one on CPU (a single-core host
        gains nothing from virtual sharding, and an explicit ``device=cpu``
        run must not enumerate the TPU)."""
        from ..parallel.mesh import get_mesh
        n = self.args.get("mesh_devices")
        if n is not None:
            return get_mesh(n_devices=int(n))
        return get_mesh(n_devices=1) if self.device == "cpu" else get_mesh()

    def feature_stream(self, runner, depth: int = 4, on_result=None):
        """Async dispatch stream over ``runner`` (parallel/mesh.py
        FeatureStream). When show_pred needs per-batch host values, the
        stream degrades to synchronous (depth=0) with ``on_result`` fired
        per batch — one code path either way."""
        if self.show_pred and on_result is not None:
            return runner.stream(depth=0, callback=on_result)
        return runner.stream(depth=depth)

    def _resolve_resize_mode(self, args: Config,
                             device_capable: bool = True) -> str:
        """Shared ``resize=auto|host|device`` validation + the per-source-
        resolution runner cache used by every device-resize pipeline
        (frame-wise, flow, i3d): a lock-guarded (video_workers share it)
        FIFO-bounded dict keyed by source (h, w).

        ``auto`` (the config default since the defaults flip) resolves to
        ``device`` — the measured ~3x host frame-rate lever, within 2 LSB
        of PIL (docs/performance.md §"Device resize") — for file-sink runs
        of families with a fused device resize, and falls back to ``host``
        for ``print``/``show_pred`` runs (the interactive/parity paths,
        which need host-side frames) and for ``device_capable=False``
        families (e.g. a flow family without ``side_size`` has no resize
        in the pipeline at all). Explicit ``host``/``device`` are honored
        as before."""
        import threading
        mode = args.get("resize") or "auto"
        if mode not in ("auto", "host", "device"):
            raise NotImplementedError(f"resize={mode!r}: expected 'auto', "
                                      "'host' or 'device'")
        self._resize_runners: Dict = {}
        self._resize_lock = threading.Lock()
        if mode == "auto":
            save_sink = self.on_extraction in ("save_numpy", "save_pickle")
            mode = ("device" if device_capable and save_sink
                    and not self.show_pred else "host")
        return mode

    def _cached_resize_runner(self, key, build):
        """Build-once per source resolution, bounded to 8 executables."""
        with self._resize_lock:
            runner = self._resize_runners.get(key)
            if runner is None:
                if len(self._resize_runners) >= 8:
                    self._resize_runners.pop(
                        next(iter(self._resize_runners)), None)
                runner = self._resize_runners[key] = build()
            return runner

    def _resolve_ingest(self, args: Config, default: str) -> str:
        """Validate the host->device wire format against the subclass's
        ``supported_ingest`` (shared by the clip-stack and frame-wise
        pipelines — see their class docs for the format semantics)."""
        ingest = args.get("ingest") or default
        if ingest not in getattr(self, "supported_ingest", ()):
            raise NotImplementedError(
                f"ingest={ingest!r}; {type(self).__name__} supports "
                f"{self.supported_ingest}")
        return ingest

    # -- lifecycle ---------------------------------------------------------
    def feature_cache(self):
        """This extractor's content-addressed cache handle (cache.py), or
        None when ``cache=false``. Built once, lazily: the fingerprints
        need the subclass's resolved attributes and weights capture."""
        if not self._cache_built:
            self._cache_built = True
            if self.cache_enabled:
                from ..cache import FeatureCache
                self._cache = FeatureCache.for_extractor(self)
        return self._cache

    def _extract(self, video_path: str) -> Optional[Dict[str, np.ndarray]]:
        from .. import telemetry
        if not self._compile_cache_checked:
            # before the first compile, after every resolved attribute
            # exists — the same lazy point the feature cache uses
            self._compile_cache_checked = True
            from ..compile_cache import attach_for_extractor
            attach_for_extractor(self)
        if not self._roofline_checked:
            self._roofline_checked = True
            from ..telemetry.roofline import ensure_for_extractor
            ensure_for_extractor(self)
        # Precedence: cache hit > filename skip (docs/performance.md).
        # The cache key proves the CONTENT + config + weights match; the
        # filename skip only proves a file with the right name loads —
        # so a hit re-serves through the sink path (which still skips the
        # physical write when the files already exist), keeping outputs
        # correct even when a stale same-stem file is present.
        cache = self.feature_cache()
        if cache is not None:
            feats = cache.lookup(video_path, self.output_feat_keys)
            if feats is not None:
                telemetry.inc("vft_cache_hit_total",
                              family=str(self.feature_type))
                telemetry.annotate(cache="hit")
                self.action_on_extraction(feats, video_path)
                return feats
        if sinks.is_already_exist(self.on_extraction, self.output_path,
                                  video_path, self.output_feat_keys):
            # work avoided WITHOUT consulting cache content: the same
            # bypass counter fires whether cache=true (a miss that the
            # filename contract absorbed) or cache=false, so
            # telemetry_report can always show WHY work was avoided
            telemetry.inc("vft_cache_bypass_total",
                          family=str(self.feature_type))
            telemetry.annotate(cache="bypass")
            return None
        if cache is not None:
            telemetry.inc("vft_cache_miss_total",
                          family=str(self.feature_type))
            telemetry.annotate(cache="miss")
        feats = self.extract(video_path)
        self.action_on_extraction(feats, video_path)
        if cache is not None:
            # store AFTER the sink path: the health gate (NaN/Inf ->
            # POISON) and any sink failure must keep bad features out of
            # the store exactly as they keep them off disk. A store
            # FAILURE, though, is contained: the artifacts are already
            # durable, and failing (or retrying) the whole video over a
            # cache write would turn an optimization into a liability —
            # the atomic entry write guarantees no torn entry was left
            try:
                cache.store(video_path, feats)
            except Exception as e:
                telemetry.inc("vft_cache_store_failures_total",
                              family=str(self.feature_type))
                print(f"cache: store failed for {video_path} "
                      f"({type(e).__name__}: {e}) — features are on disk, "
                      "entry skipped")
        return feats

    def extract(self, video_path: str) -> Dict[str, np.ndarray]:
        raise NotImplementedError

    def action_on_extraction(self, feats: Dict[str, np.ndarray],
                             video_path: str) -> None:
        if self.health:
            # digest + gate BEFORE any sink write: a non-finite feature
            # raises (POISON) so it journals/quarantines instead of being
            # silently persisted; the digest record of the bad tensor is
            # already in _health.jsonl for the post-mortem
            from ..telemetry import health
            from ..utils.profiling import profiler
            with profiler.stage("health"):
                health.check_features(feats, video_path, self.feature_type,
                                      self.output_path)
        if self.parity:
            # head seam: the per-key feature tensors exactly as the sink
            # is about to persist them (certify's in-process arms tap
            # this seam themselves off the extract() return)
            from ..telemetry import parity as _parity
            for key in sorted(feats):
                _parity.tap("head", key, feats[key], video=str(video_path),
                            feature_type=self.feature_type)
        # re-check before overwrite: another worker may have just written it
        # (reference base_extractor.py:72-76)
        if self.on_extraction != "print" and sinks.is_already_exist(
                self.on_extraction, self.output_path, video_path,
                self.output_feat_keys):
            return
        sinks.action_on_extraction(feats, video_path, self.output_path,
                                   self.on_extraction)
