"""Config system: per-feature YAML defaults + CLI dotlist overrides.

Re-designed equivalent of the reference's OmegaConf flow (reference
main.py:9-10, utils/utils.py:71-125,218-229) without the OmegaConf dependency:
plain-YAML defaults in ``video_features_tpu/configs/<feature_type>.yml`` merged
under a parsed ``key=value`` dotlist (CLI wins), then validated and
path-patched by :func:`sanity_check`.

Differences from the reference, by design:
  - ``device`` is ``tpu`` / ``cpu`` / ``auto`` (default). ``cuda*`` values are
    accepted for drop-in compatibility and mapped to ``auto`` with a warning
    (the reference falls back cuda->cpu at utils/utils.py:84-86).
  - PWC-Net runs everywhere (the reference requires a GPU,
    utils/utils.py:104-105, because its correlation is a CuPy CUDA kernel; ours
    is a Pallas/XLA kernel with a pure-XLA interpret path on CPU).
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Union

import yaml

_CONFIG_DIR = Path(__file__).resolve().parent / "configs"

#: everything the program builds or caches by default (XLA executables, the
#: native writer's .so) goes under this one directory inside the checkout,
#: listed in .gitignore — a fixed path, because a cache that moves never
#: hits, and not $HOME, which the machine may not keep
CACHE_ROOT = Path(__file__).resolve().parent.parent / ".cache"

#: validated config keys that legitimately appear in only SOME family
#: YAMLs — family-specific defaults (flow nets have iteration counts,
#: clip-stack families have windowing, CLIP has a text side). ``vft-lint``
#: rule VFT002 requires every validator-read key to be carried by ALL
#: eight YAMLs unless it is declared here (or in LAUNCH_KEYS below):
#: a key that is neither is a default nobody documented.
OPTIONAL_KEYS = frozenset({
    "batch_size", "bpe_path", "clip_batch_size",
    "extraction_fps", "extraction_total", "finetuned_on", "flow_iters",
    "flow_model_weights_path", "flow_stack_batch", "flow_type",
    "flow_weights_path", "fps_mode", "frontend", "ingest",
    "iters", "model_name", "model_parallel", "pca_weights_path",
    "postprocess", "pred_texts", "resize", "resize_to_smaller_edge",
    "side_size", "stack_size", "step_size", "streams", "vision_attn",
})

#: launch-time keys that never ride a family YAML: serve/gateway spool
#: plumbing passed on the vft-serve/vft-gateway command line, and expert
#: decode-pipeline knobs that are deliberately undocumented defaults.
#: Declared so VFT002 can tell "launch-only by design" from "typo'd key
#: nobody validates".
LAUNCH_KEYS = frozenset({
    # profiling hooks (cli.py)
    "profile", "profile_trace_dir",
    # expert decode-pipeline knobs (extractors/base.py, multi.py)
    "video_decode", "decode_workers", "decode_depth", "fanout_depth",
    "cross_video_batching",
    # vft-serve launch keys (serve.py; serve_slo_s rides the YAMLs)
    "spool_dir", "serve_max_pending", "serve_poll_interval_s",
    "serve_idle_exit_s", "serve_max_requests", "serve_workers",
    "serve_warmup_video",
    # vft-gateway launch keys (gateway.py validate_gateway_args)
    "gateway_tenants", "gateway_port", "gateway_host",
    "gateway_max_queued", "gateway_spool_bound", "gateway_max_body_mb",
    "gateway_poll_interval_s", "gateway_expire_grace_s",
    "gateway_default_timeout_s",
    # vft-gc launch keys (gc.py validate_gc_args)
    "gc", "gc_quota_gb", "gc_cache_retention_s",
    "gc_compile_retention_s", "gc_spool_retention_s",
    "gc_inbox_retention_s", "gc_incident_retention_s",
    "gc_quarantine_retention_s", "gc_staging_retention_s",
    "gc_interval_s",
})

#: removed reference flags: accepted, warned about and deleted by
#: sanity_check — exempt from every other key contract.
REMOVED_KEYS = frozenset({"device_ids"})


class Config(dict):
    """A dict with attribute access, nesting-aware, YAML-serializable.

    Stands in for OmegaConf's DictConfig in the reference API surface.
    """

    def __getattr__(self, name: str) -> Any:
        try:
            return self[name]
        except KeyError as e:
            raise AttributeError(name) from e

    def __setattr__(self, name: str, value: Any) -> None:
        self[name] = value

    def __delattr__(self, name: str) -> None:
        try:
            del self[name]
        except KeyError as e:
            raise AttributeError(name) from e

    @staticmethod
    def _wrap(value: Any) -> Any:
        if isinstance(value, dict) and not isinstance(value, Config):
            return Config({k: Config._wrap(v) for k, v in value.items()})
        if isinstance(value, list):
            return [Config._wrap(v) for v in value]
        return value

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        for k, v in list(self.items()):
            super().__setitem__(k, Config._wrap(v))

    def __setitem__(self, key, value):
        super().__setitem__(key, Config._wrap(value))

    def to_yaml(self) -> str:
        return yaml.safe_dump(_plain(self), sort_keys=False)


def _plain(obj: Any) -> Any:
    if isinstance(obj, dict):
        return {k: _plain(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_plain(v) for v in obj]
    return obj


def build_cfg_path(feature_type: str) -> Path:
    """Path of the YAML defaults for a feature family.

    Mirrors reference utils/utils.py:218-229 but resolves inside the installed
    package instead of the current working directory.
    """
    path = _CONFIG_DIR / f"{feature_type}.yml"
    return path


def load_yaml(path: Union[str, os.PathLike]) -> Config:
    with open(path) as f:
        data = yaml.safe_load(f) or {}
    return Config(data)


def parse_dotlist(argv: Sequence[str]) -> Config:
    """Parse ``key=value`` CLI arguments (OmegaConf.from_cli equivalent).

    Values go through YAML, so ``batch_size=16`` is an int, ``flow_type=null``
    is None, ``video_paths=[a.mp4,b.mp4]`` is a list. Dots nest:
    ``a.b=1`` -> ``{'a': {'b': 1}}``.
    """
    out: Dict[str, Any] = {}
    for arg in argv:
        if "=" not in arg:
            raise ValueError(
                f"CLI arguments must look like key=value (got {arg!r})")
        key, raw = arg.split("=", 1)
        try:
            value = yaml.safe_load(raw) if raw != "" else None
        except yaml.YAMLError:
            value = raw
        node = out
        parts = key.split(".")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = value
    return Config(out)


def merge(base: Config, override: Config) -> Config:
    """Deep merge; ``override`` wins (OmegaConf.merge semantics we rely on)."""
    result = Config(dict(base))
    for k, v in override.items():
        if k in result and isinstance(result[k], dict) and isinstance(v, dict):
            result[k] = merge(result[k], v)
        else:
            result[k] = v
    return result


def load_config(feature_type: str,
                overrides: Optional[Union[Config, Dict[str, Any]]] = None,
                ) -> Config:
    """YAML defaults for ``feature_type`` merged under ``overrides``."""
    cfg_path = build_cfg_path(feature_type)
    if not cfg_path.exists():
        raise FileNotFoundError(
            f"Unknown feature_type {feature_type!r}: no config at {cfg_path}")
    cfg = load_yaml(cfg_path)
    if overrides:
        cfg = merge(cfg, Config(dict(overrides)))
    return cfg


def load_multi_config(families: Sequence[str],
                      overrides: Optional[Union[Config, Dict[str, Any]]] = None,
                      ) -> "Dict[str, Config]":
    """Per-family configs for a multi-family run (ordered like ``families``).

    Override routing: top-level CLI keys are SHARED (merged into every
    family's YAML defaults); a key nested under a requested family name is
    that family's private override and wins over the shared layer —
    ``feature_type=resnet,clip extraction_fps=1 clip.extraction_fps=2``
    runs resnet at 1 fps and clip at 2. A nested override for a known
    family that is NOT requested is almost certainly a typo'd run and
    fails loudly instead of silently extracting nothing for it.
    """
    from .registry import _DISPATCH
    families = list(families)
    overrides = Config(dict(overrides or {}))
    shared = {k: v for k, v in overrides.items()
              if k != "feature_type" and k not in families}
    for k in list(shared):
        if k in _DISPATCH and isinstance(shared[k], dict):
            raise ValueError(
                f"per-family override block {k}.* given, but {k!r} is not "
                f"in feature_type={','.join(families)} — add it to the "
                "list or drop the override")
    per: Dict[str, Config] = {}
    for f in families:
        fam_over = overrides.get(f)
        merged = Config(dict(shared))
        if isinstance(fam_over, dict):
            merged = merge(merged, Config(dict(fam_over)))
        cfg = load_config(f, merged)
        cfg.feature_type = f
        per[f] = cfg
    return per


def sanity_check_multi(per_family: "Dict[str, Config]", *,
                       require_videos: bool = True) -> None:
    """Multi-family constraints, then the normal per-family sanity_check
    (which namespaces each family's output/tmp paths under its own
    ``feature_type[/model_name]`` subdir — so sinks and journals never
    collide across families)."""
    for f, args in per_family.items():
        if args.get("on_extraction", "print") == "print":
            raise ValueError(
                "multi-family extraction needs a file sink "
                "(on_extraction=save_numpy or save_pickle): N families' "
                "print dumps would interleave, and the per-family skip/"
                "journal contracts need per-family output dirs")
        if args.get("show_pred"):
            raise ValueError(
                "show_pred=true is unsupported in multi-family runs "
                "(per-batch prediction printing would interleave across "
                "families)")
        if (args.get("fps_mode", "select") or "select") == "reencode":
            raise ValueError(
                "fps_mode=reencode is unsupported in multi-family runs: "
                "each family's reencode provenance is its own lossy "
                "temp-file decode, which cannot share one pass — run "
                "golden-parity extractions one family at a time")
        sanity_check(args, require_videos=require_videos)


def resolve_device(device: Optional[str]) -> str:
    """Map a user device string to 'tpu' or 'cpu'.

    Accepts 'auto' (default), 'tpu', 'cpu', and legacy 'cuda*' strings, which
    are treated as 'auto' for drop-in compatibility with reference configs.
    """
    if device is None:
        device = "auto"
    device = str(device)
    if device.startswith("cuda"):
        print(f"device={device!r} is a CUDA ordinal from the reference CLI; "
              "this framework targets TPU. Treating it as device=auto.")
        device = "auto"
    if device in ("tpu", "cpu"):
        # an explicit choice never enumerates devices here: `device=cpu`
        # must not claim a chip on a TPU host, and `device=tpu` is verified
        # against the backend where it is used (extractors/base.py)
        return device
    if device != "auto":
        raise ValueError(f"Unsupported device {device!r}; use tpu|cpu|auto")
    import jax
    devices = jax.devices()
    if any(d.platform == "tpu" for d in devices):
        return "tpu"
    print(f"device=auto: JAX found no TPU (devices: {devices}); running on "
          "the CPU")
    return "cpu"


def sanity_check(args: Config, *, require_videos: bool = True) -> None:
    """Validate user arguments and patch output/tmp paths in place.

    ``require_videos=False`` (vft-serve, serve.py) skips the launch-time
    video-list validation: a server has no corpus at launch — videos
    arrive per request, and per-request failures route through the
    normal per-video fault isolation instead of a launch assert.

    Reproduces the semantics of reference utils/utils.py:71-125:
      - one of video_paths / file_with_video_paths required
      - unique video stems (the output filename contract collides otherwise)
      - output_path != tmp_path
      - i3d stack_size >= 10
      - batch_size must not be None when present
      - extraction_fps / extraction_total mutually exclusive
      - output_path & tmp_path get ``feature_type[/model_name]`` appended with
        '/' replaced by '_' (e.g. CLIP's ViT-B/32 -> ViT-B_32)

    Dropped on purpose: the cuda->cpu fallback (resolve_device handles device
    naming) and the PWC-needs-GPU assert (our PWC correlation is Pallas/XLA).
    """
    from .utils.lists import form_list_from_user_input

    if "device_ids" in args:
        print("WARNING: `device_ids` is a removed reference flag; single-host "
              "multi-chip execution here is automatic over the TPU mesh. "
              "Ignoring it.")
        del args["device_ids"]
    args.device = resolve_device(args.get("device"))

    if require_videos:
        assert args.get("file_with_video_paths") or args.get("video_paths"), \
            "`video_paths` or `file_with_video_paths` must be specified"
        filenames = [Path(p).stem for p in form_list_from_user_input(
            args.get("video_paths"), args.get("file_with_video_paths"),
            to_shuffle=False)]
        assert len(filenames) == len(set(filenames)), \
            "Non-unique video file stems: outputs would overwrite each " \
            "other (same contract as reference video_features issue #54)"
    assert os.path.relpath(str(args.output_path)) != os.path.relpath(str(args.tmp_path)), \
        "The same path for out & tmp"

    if args.get("show_pred") and args.feature_type == "vggish":
        print("Showing class predictions is not implemented for VGGish")

    vw = args.get("video_workers") or 1
    if isinstance(vw, str):
        vw = vw.strip().lower()
        if vw != "auto":
            raise ValueError(f"video_workers={vw!r}: expected an int or "
                             "'auto'")
        args.video_workers = vw
    if (vw == "auto" or int(vw) > 1) and (
            args.get("on_extraction", "print") == "print"
            or args.get("show_pred")):
        # concurrent videos would interleave their stdout dumps line-by-line
        print("WARNING: video_workers > 1 with on_extraction=print or "
              "show_pred would interleave per-video output; forcing "
              "video_workers=1. Use save_numpy/save_pickle for pipelined "
              "multi-video extraction.")
        args.video_workers = 1

    if args.feature_type == "i3d" and args.get("stack_size") is not None:
        assert args.stack_size >= 10, (
            "I3D model does not support inputs shorter than 10 timestamps. "
            f"You have: {args.stack_size}")

    if "batch_size" in args:
        assert args.batch_size is not None, \
            f"Please specify `batch_size`. It is {args.batch_size} now"

    if "extraction_fps" in args and "extraction_total" in args:
        assert not (args.get("extraction_fps") is not None
                    and args.get("extraction_total") is not None), \
            "`extraction_fps` and `extraction_total` are mutually exclusive"

    # fault-tolerance keys (utils/faults.py RetryPolicy.from_config):
    # validated at launch so a typo fails before N videos burn retries
    ra = args.get("retry_attempts")
    if ra is not None and int(ra) < 1:
        raise ValueError(f"retry_attempts={ra!r}: need an int >= 1")
    rb = args.get("retry_backoff_s")
    if rb is not None and float(rb) < 0:
        raise ValueError(f"retry_backoff_s={rb!r}: need a float >= 0")
    vd = args.get("video_deadline_s")
    if vd is not None and float(vd) <= 0:
        raise ValueError(f"video_deadline_s={vd!r}: need a float > 0 "
                         "(or null to disable the per-video deadline)")

    # telemetry keys (telemetry/ subsystem): same launch-time validation
    tel = args.get("telemetry", False)
    if not isinstance(tel, bool):
        raise ValueError(f"telemetry={tel!r}: expected true or false")
    mi = args.get("metrics_interval_s")
    if mi is not None and float(mi) <= 0:
        raise ValueError(f"metrics_interval_s={mi!r}: need a float > 0 "
                         "(the heartbeat/metrics flush period)")
    tr = args.get("trace", False)
    if not isinstance(tr, bool):
        raise ValueError(f"trace={tr!r}: expected true or false (writes "
                         "{output_path}/_trace.json, telemetry/trace.py)")
    he = args.get("health", False)
    if not isinstance(he, bool):
        raise ValueError(f"health={he!r}: expected true or false (digests "
                         "features into {output_path}/_health.jsonl and "
                         "quarantines NaN/Inf outputs, telemetry/health.py)")
    pa = args.get("parity", False)
    if not isinstance(pa, bool):
        raise ValueError(f"parity={pa!r}: expected true or false (per-seam "
                         "numerics digests into {output_path}/_parity.jsonl, "
                         "telemetry/parity.py — render with vft-parity)")
    rf = args.get("roofline", False)
    if not isinstance(rf, bool):
        raise ValueError(f"roofline={rf!r}: expected true or false (MFU "
                         "accounting into {output_path}/_roofline.json, "
                         "telemetry/roofline.py — render with vft-roofline)")
    hi = args.get("history", False)
    if not isinstance(hi, bool):
        raise ValueError(f"history={hi!r}: expected true or false (retained "
                         "heartbeat samples in {output_path}/"
                         "_history_{host_id}.jsonl, telemetry/history.py)")
    al = args.get("alerts", False)
    if not isinstance(al, bool):
        raise ValueError(f"alerts={al!r}: expected true or false (alert "
                         "rules on the heartbeat cadence into "
                         "{output_path}/_alerts.jsonl + _incidents/ "
                         "bundles, telemetry/alerts.py — render with "
                         "vft-alert)")
    if (hi or al) and not args.get("telemetry", False):
        raise ValueError(
            "history=true / alerts=true need telemetry=true: samples and "
            "rule evaluation ride the heartbeat cadence "
            "(docs/observability.md 'Alerting & incident bundles')")

    # feature-cache keys (cache.py): validated at launch like the
    # telemetry switches — a typo'd cache flag must not silently run cold
    ca = args.get("cache", False)
    if not isinstance(ca, bool):
        raise ValueError(f"cache={ca!r}: expected true or false (the "
                         "content-addressed feature cache, cache.py)")
    cd = args.get("cache_dir")
    if cd is not None and not isinstance(cd, str):
        raise ValueError(f"cache_dir={cd!r}: expected a directory path or "
                         "null (null -> VFT_CACHE_DIR or "
                         "~/.cache/video_features_tpu/feature_cache)")
    cs = args.get("cache_scope", "shared") or "shared"
    if cs not in ("shared", "tenant"):
        raise ValueError(f"cache_scope={cs!r}: expected 'shared' (one "
                         "entry per content — cross-tenant dedup, the "
                         "dominant win at scale) or 'tenant' (the "
                         "requesting tenant salts the key: no tenant "
                         "ever observes a hit on another's content — "
                         "docs/serving.md)")

    # gateway keys (gateway.py): tenant table, port, admission bounds —
    # full validation lives with the gateway so vft-gateway and any
    # serve/cli run carrying gateway_* keys fail a typo identically
    if any(str(k).startswith("gateway_") for k in args):
        from .gateway import validate_gateway_args
        validate_gateway_args(args)

    # storage lifecycle keys (gc.py): quotas/retentions — full validation
    # lives with the GC plane so vft-gc and any run carrying gc keys
    # fail a typo identically
    if "gc" in args or any(str(k).startswith("gc_") for k in args):
        from .gc import validate_gc_args
        validate_gc_args(args)

    # compile-cache keys (compile_cache.py): the fleet-shared persistent
    # XLA store — a typo'd switch must not silently compile cold forever
    cc = args.get("compile_cache", "auto")
    if cc not in (True, False, "auto"):
        raise ValueError(f"compile_cache={cc!r}: expected true, false or "
                         "'auto' ('auto' = on for TPU runs; CPU runs need "
                         "an explicit compile_cache_dir — "
                         "docs/performance.md 'Never compile twice, fleet "
                         "edition')")
    ccd = args.get("compile_cache_dir")
    if ccd is not None and not isinstance(ccd, str):
        raise ValueError(f"compile_cache_dir={ccd!r}: expected a directory "
                         "path or null (null -> VFT_COMPILE_CACHE_DIR or "
                         "<checkout>/.cache/xla; JAX_COMPILATION_CACHE_DIR, "
                         "where set, overrides all of them)")

    # fleet scheduling keys (parallel/queue.py): validated at launch —
    # a typo'd fleet mode must fail before N hosts start claiming
    fl = args.get("fleet", "static") or "static"
    if fl not in ("static", "queue"):
        raise ValueError(f"fleet={fl!r}: expected 'static' (md5 hash "
                         "sharding fixed at launch) or 'queue' (the "
                         "work-stealing lease queue, docs/fleet.md)")
    if fl == "queue":
        if not args.get("telemetry", False):
            raise ValueError(
                "fleet=queue needs telemetry=true: the heartbeat flusher "
                "thread renews work-item leases and heartbeats are the "
                "fleet membership/liveness signal (docs/fleet.md)")
        if args.get("on_extraction", "print") == "print":
            raise ValueError(
                "fleet=queue needs a file sink (on_extraction=save_numpy "
                "or save_pickle): stolen work relies on the idempotent "
                "skip-if-exists output contract, which print lacks")
    fls = args.get("fleet_lease_s")
    if fls is not None and float(fls) <= 0:
        raise ValueError(f"fleet_lease_s={fls!r}: need a float > 0 (the "
                         "work-item lease period; renewed every heartbeat)")
    fmr = args.get("fleet_max_reclaims")
    if fmr is not None and int(fmr) < 1:
        raise ValueError(f"fleet_max_reclaims={fmr!r}: need an int >= 1 "
                         "(reclaims before an item is quarantined)")
    fca = args.get("fleet_canary", False)
    if not isinstance(fca, bool):
        raise ValueError(f"fleet_canary={fca!r}: expected true or false "
                         "(gate joining hosts on a re-extracted slice, "
                         "docs/fleet.md)")

    # serve SLO key (serve.py): the per-request latency objective in
    # seconds, measured queue-wait + service; a typo'd objective must
    # fail at launch, not silently count zero violations
    slo = args.get("serve_slo_s")
    if slo is not None:
        try:
            slo_f = float(slo)
        except (TypeError, ValueError):
            raise ValueError(f"serve_slo_s={slo!r}: need a float > 0 in "
                             "seconds, or null to disable violation "
                             "counting (docs/serving.md)") from None
        if slo_f <= 0:
            raise ValueError(f"serve_slo_s={slo!r}: need a float > 0 in "
                             "seconds, or null to disable violation "
                             "counting (docs/serving.md)")

    # fault-injection plan (utils/inject.py): the full plan grammar is
    # parsed at launch, so a typo'd site/fault/trigger fails HERE with
    # the offending clause named — never silently runs a chaos-free
    # "chaos" run (docs/chaos.md)
    inj = args.get("inject")
    if inj is not None:
        if not isinstance(inj, str):
            raise ValueError(
                f"inject={inj!r}: expected a plan string like "
                "'seed=1;sink.fsync=enospc@n1' or null (docs/chaos.md)")
        from .utils.inject import parse_plan
        parse_plan(inj)  # raises ValueError naming the bad clause

    # resize=auto|host|device (extractors/base.py _resolve_resize_mode):
    # 'auto' (the default) picks 'device' for save sinks and 'host' for
    # print/show_pred and for families without a fused device resize
    rz = args.get("resize")
    if rz is not None and rz not in ("auto", "host", "device"):
        raise ValueError(f"resize={rz!r}: expected 'auto', 'host' or "
                         "'device'")

    fps_mode = args.get("fps_mode", "select") or "select"
    if fps_mode not in ("select", "reencode"):
        raise ValueError(
            f"fps_mode={fps_mode!r}: expected 'select' (bit-exact source "
            "frames, the default) or 'reencode' (the reference's lossy "
            "temp-file decode path, for golden-parity runs)")

    # Namespace outputs under feature_type[/model_name], '/'->'_'
    # (reference utils/utils.py:112-125).
    subs: List[str] = [args.feature_type]
    if "model_name" in args and args.model_name is not None:
        subs.append(str(args.model_name))
    out, tmp = str(args.output_path), str(args.tmp_path)
    for p in subs:
        out = os.path.join(out, p.replace("/", "_"))
        tmp = os.path.join(tmp, p.replace("/", "_"))
    args.output_path = out
    args.tmp_path = tmp
