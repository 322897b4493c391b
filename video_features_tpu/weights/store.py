"""Weight resolution: find, convert and cache parameters for a model key.

The reference gets weights from four places (SURVEY §2.5): local ``.pt/.pth``
files in the repo, torchvision/torch.hub downloads, the OpenAI CDN (CLIP) and
GitHub releases (VGGish). This environment has no network egress, so the
story is:

  1. an explicit ``weights_path`` in the config — a torch checkpoint (``.pt``,
     ``.pth``) converted on the fly, or an already-converted ``.msgpack``;
  2. the ``VFT_WEIGHTS_DIR`` directory (default
     ``~/.cache/video_features_tpu``): ``{model_key}.msgpack`` converted
     previously, or ``{model_key}.pt[h]`` torch blobs dropped there;
  3. the torch hub cache (``$TORCH_HOME/hub/checkpoints``) for known
     torchvision/hub filenames;
  4. on a NETWORKED host, ``VFT_FETCH_WEIGHTS=1`` enables an in-process
     download from the same upstream sources the reference uses (OpenAI CDN
     with full SHA-256 pinning, reference models/clip/clip_src/clip.py:32-74;
     torchvggish GitHub releases, vggish_slim.py:122-127; torch-hub /
     torchvision CDN, extract_r21d.py:105-113), refusing on digest mismatch;
  5. random initialization — only if ``allow_random_weights`` is set (tests,
     dry runs, benchmarks that only measure throughput).
"""
from __future__ import annotations

import os
import time
from pathlib import Path
from typing import Any, Callable, Dict, Optional

# known torch-hub / CDN filenames per model key, for cache probing
HUB_FILENAMES: Dict[str, tuple] = {
    "resnet18": ("resnet18-f37072fd.pth", "resnet18-5c106cde.pth"),
    "resnet34": ("resnet34-b627a593.pth", "resnet34-333f7ec4.pth"),
    "resnet50": ("resnet50-0676ba61.pth", "resnet50-19c8e357.pth"),
    "resnet101": ("resnet101-63fe2227.pth", "resnet101-5d3b4d8f.pth"),
    "resnet152": ("resnet152-394f9c45.pth", "resnet152-b121ed2d.pth"),
    "r2plus1d_18_16_kinetics": ("r2plus1d_18-91a641e6.pth",),
    "r2plus1d_34_32_ig65m_ft_kinetics": ("r2plus1d_34_clip32_ig65m_from_scratch-449a7af9.pth",),
    "r2plus1d_34_8_ig65m_ft_kinetics": ("r2plus1d_34_clip8_ig65m_from_scratch-9bae36ae.pth",),
    # repo-local checkpoints in the reference (SURVEY §2.5); same filenames
    # accepted if dropped into VFT_WEIGHTS_DIR
    "raft_sintel": ("raft-sintel.pth",),
    "raft_kitti": ("raft-kitti.pth",),
    "i3d_rgb": ("i3d_rgb.pt",),
    "i3d_flow": ("i3d_flow.pt",),
    "s3d_kinetics400": ("S3D_kinetics400_torchified.pt",),
    "pwc_sintel": ("pwc_net_sintel.pt",),
    # torchvggish GitHub release filenames (reference vggish_slim.py:122-127)
    "vggish": ("vggish-10086976.pth",),
    "vggish_pca": ("vggish_pca_params-970ea276.pth", "vggish_pca_params.npz"),
    # OpenAI CDN filenames (reference clip_src/clip.py:32-42); TorchScript
    # archives are unwrapped by torch_import.load_torch_state_dict
    "clip_RN50": ("RN50.pt",),
    "clip_RN101": ("RN101.pt",),
    "clip_RN50x4": ("RN50x4.pt",),
    "clip_RN50x16": ("RN50x16.pt",),
    "clip_RN50x64": ("RN50x64.pt",),
    "clip_ViT-B-32": ("ViT-B-32.pt",),
    "clip_ViT-B-16": ("ViT-B-16.pt",),
    "clip_ViT-L-14": ("ViT-L-14.pt",),
    "clip_ViT-L-14-336px": ("ViT-L-14-336px.pt",),
}

#: full published SHA-256 digests: the OpenAI CDN embeds them in the
#: download URL path and the reference's _download() verifies exactly this
#: digest (reference models/clip/clip_src/clip.py:32-42,61-73)
CLIP_SHA256: Dict[str, str] = {
    "RN50.pt": "afeb0e10f9e5a86da6080e35cf09123aca3b358a0c3e3b6c78a7b63bc04b6762",
    "RN101.pt": "8fa8567bab74a42d41c5915025a8e4538c3bdbe8804a470a72f30b0d94fab599",
    "RN50x4.pt": "7e526bd135e493cef0776de27d5f42653e6b4c8bf9e0f653bb11773263205fdd",
    "RN50x16.pt": "52378b407f34354e150460fe41077663dd5b39c54cd0bfd2b27167a4a06ec9aa",
    "RN50x64.pt": "be1cfb55d75a9666199fb2206c106743da0f6468c9d327f3e0d0a543a9919d9c",
    "ViT-B-32.pt": "40d365715913c9da98579312b702a82c18be219cc2a73407c4526f58eba950af",
    "ViT-B-16.pt": "5806e77cd80f8b59890b7e101eabd078d9fb84e6937f9e85e4ecb61988df416f",
    "ViT-L-14.pt": "b8cca3fd41ae0c99ba7e8951adf17d267cdb84cd88be6f7c2e0eca1737a03836",
    "ViT-L-14-336px.pt": "3035c92b350959924f9f00213499208652fc7ea050643e8b385c2dac08641f02",
}

_TORCH_CDN = "https://download.pytorch.org/models/"
_IG65M = "https://github.com/moabitcoin/ig65m-pytorch/releases/download/v1.0.0/"
_VGGISH = "https://github.com/harritaylor/torchvggish/releases/download/v0.1/"
#: the reference vendors these blobs inside its own git tree
#: (.MISSING_LARGE_BLOBS); raw-file URLs are the only public source.
#: These are PICKLED torch checkpoints with no published digest, so a
#: mutable branch ref is an arbitrary-code-execution hazard: a moved or
#: compromised branch swaps the bytes under the same URL. Downloads
#: therefore require an immutable commit pin (``VFT_REF_COMMIT=<sha>``,
#: resolved at import so the URLs themselves are immutable); without one
#: the fetcher REFUSES these files unless ``VFT_ALLOW_MUTABLE_REF=1``
#: explicitly accepts the old master-ref behavior. Either way the first
#: successful fetch records the file's SHA-256 into
#: ``{weights_dir}/ref_digests.json`` and every later fetch verifies
#: against it (trust-on-first-use), so a silently-moved blob can never
#: replace an already-trusted one.
_REF_COMMIT = os.environ.get("VFT_REF_COMMIT", "")
_REF_RAW = ("https://github.com/habakan/video_features/raw/"
            f"{_REF_COMMIT or 'master'}/")
#: upstream filenames served from the reference repo's git tree (the
#: unpinned-pickle set the mutable-ref refusal above applies to)
REF_FILES = frozenset({
    "raft-sintel.pth", "raft-kitti.pth", "i3d_rgb.pt", "i3d_flow.pt",
    "S3D_kinetics400_torchified.pt", "pwc_net_sintel.pt",
})

#: upstream URL per filename — the same sources the reference downloads
#: from (or, for repo-local blobs, vendors)
WEIGHT_URLS: Dict[str, str] = {
    **{f: _TORCH_CDN + f for key in ("resnet18", "resnet34", "resnet50",
                                     "resnet101", "resnet152",
                                     "r2plus1d_18_16_kinetics")
       for f in HUB_FILENAMES[key]},
    **{f: _IG65M + f for key in ("r2plus1d_34_32_ig65m_ft_kinetics",
                                 "r2plus1d_34_8_ig65m_ft_kinetics")
       for f in HUB_FILENAMES[key]},
    "vggish-10086976.pth": _VGGISH + "vggish-10086976.pth",
    "vggish_pca_params-970ea276.pth": _VGGISH + "vggish_pca_params-970ea276.pth",
    **{f: f"https://openaipublic.azureedge.net/clip/models/{sha}/{f}"
       for f, sha in CLIP_SHA256.items()},
    "raft-sintel.pth": _REF_RAW + "models/raft/checkpoints/raft-sintel.pth",
    "raft-kitti.pth": _REF_RAW + "models/raft/checkpoints/raft-kitti.pth",
    "i3d_rgb.pt": _REF_RAW + "models/i3d/checkpoints/i3d_rgb.pt",
    "i3d_flow.pt": _REF_RAW + "models/i3d/checkpoints/i3d_flow.pt",
    "S3D_kinetics400_torchified.pt":
        _REF_RAW + "models/s3d/checkpoint/S3D_kinetics400_torchified.pt",
    "pwc_net_sintel.pt": _REF_RAW + "models/pwc/checkpoints/pwc_net_sintel.pt",
}


def expected_digest(fname: str):
    """``(kind, digest)`` for an upstream filename: ``'sha256'`` (full,
    CLIP CDN), ``'sha256-prefix'`` (the 8-hex tail torch-hub release names
    embed, e.g. ``resnet18-f37072fd.pth``), or ``(None, None)`` for the
    reference's repo-local blobs, which publish no digest."""
    if fname in CLIP_SHA256:
        return "sha256", CLIP_SHA256[fname]
    stem = Path(fname).stem
    if "-" in stem:
        tail = stem.rsplit("-", 1)[1]
        if len(tail) == 8 and all(c in "0123456789abcdef" for c in tail):
            return "sha256-prefix", tail
    return None, None


def _digest_registry_path() -> Path:
    return weights_dir() / "ref_digests.json"


def recorded_digest(fname: str) -> Optional[str]:
    """SHA-256 recorded for ``fname`` on a previous fetch (the
    trust-on-first-use registry for files with no published digest)."""
    import json
    try:
        with open(_digest_registry_path()) as f:
            return json.load(f).get(fname)
    except (OSError, ValueError):
        return None


def record_digest(fname: str, sha256: str) -> None:
    import json
    path = _digest_registry_path()
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        data = {}
    data[fname] = sha256
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".json.tmp")
    # vft-lint: disable=VFT004 — temp+os.replace in place; the TOFU digest registry is advisory provenance, a lost record re-records on next fetch
    with open(tmp, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
    os.replace(tmp, path)


#: a ``.part`` download older than this is litter from a fetcher that
#: died mid-stream (SIGKILL skips every unlink-on-failure handler) —
#: no live download runs this long, so the next fetch sweeps it
PART_STALE_S = 3600.0


def sweep_stale_parts(wd: Path, *, now: Optional[float] = None,
                      stale_s: float = PART_STALE_S) -> int:
    """Delete ``*.part`` temp files older than ``stale_s``. Young parts
    are left alone — a concurrent fetcher may still be streaming into
    them (the mkstemp names are per-process unique, so deleting someone
    else's LIVE part would fail their promote). Returns the count."""
    now = time.time() if now is None else float(now)
    swept = 0
    try:
        parts = sorted(Path(wd).glob("*.part"))
    except OSError:
        return 0
    for p in parts:
        try:
            if now - p.stat().st_mtime < stale_s:
                continue
            p.unlink()
            swept += 1
            print(f"weights: swept stale download litter {p.name}")
        except OSError:
            pass  # a sibling sweeper won the race, or perms: both fine
    return swept


def fetch_checkpoint(model_key: str) -> Optional[Path]:
    """Download ``model_key``'s upstream checkpoint into ``weights_dir()``,
    verifying the published SHA-256 while streaming. Mirrors the
    reference's behavior (clip.py:61-73): a digest mismatch deletes the
    file and raises — a truncated or tampered download is never usable.
    Files with no published digest (the reference's repo-local blobs)
    download with a provenance warning, matching the trust level of the
    reference's own git-hosted copies.

    Only called when ``VFT_FETCH_WEIGHTS=1`` (find_checkpoint); offline
    behavior is unchanged without the flag.
    """
    import hashlib
    import urllib.request
    wd = weights_dir()
    if wd.is_dir():
        sweep_stale_parts(wd)
    for fname in HUB_FILENAMES.get(model_key, ()):
        url = WEIGHT_URLS.get(fname)
        if url is None:
            continue
        dest = wd / fname
        kind, digest = expected_digest(fname)
        recorded = None
        if kind is None:
            if (fname in REF_FILES and not _REF_COMMIT
                    and os.environ.get("VFT_ALLOW_MUTABLE_REF") != "1"):
                raise RuntimeError(
                    f"{fname}: refusing to download a pickled checkpoint "
                    "from the MUTABLE 'master' ref of the reference repo "
                    "(torch.load is pickle — a moved or compromised branch "
                    "means arbitrary code execution). Pin an immutable "
                    "commit with VFT_REF_COMMIT=<sha>, or set "
                    "VFT_ALLOW_MUTABLE_REF=1 to accept the risk, or drop "
                    f"the file into {wd} yourself.")
            recorded = recorded_digest(fname)
            if recorded:
                print(f"{fname}: verifying against the SHA-256 recorded on "
                      f"first fetch ({_digest_registry_path()})")
            else:
                print(f"WARNING: no published digest for {fname}; "
                      f"downloading unverified from {url} (its SHA-256 "
                      "will be recorded for future fetches)")
        wd.mkdir(parents=True, exist_ok=True)
        # per-process unique temp name: concurrent fetchers sharing a
        # weights dir (multi-host launch) must never interleave writes
        # into one .part file and promote a co-written blob
        import tempfile
        fd, part_name = tempfile.mkstemp(prefix=fname + ".", suffix=".part",
                                         dir=wd)
        part = Path(part_name)
        h = hashlib.sha256()
        # wrap the fd BEFORE touching the network: if urlopen raises, the
        # with-statement still closes `out` (bare fd would leak per retry)
        # vft-lint: disable=VFT004 — verify-then-promote: the .part download is sha256-checked before the rename, a torn stream can never be promoted
        out = os.fdopen(fd, "wb")
        try:
            # socket-level timeout also bounds mid-stream read stalls — a
            # blackholed route must fail the fetch, not hang the run
            with out, urllib.request.urlopen(url, timeout=60) as src:
                while True:
                    chunk = src.read(1 << 20)
                    if not chunk:
                        break
                    h.update(chunk)
                    out.write(chunk)
        except OSError as e:  # URLError subclasses OSError
            part.unlink(missing_ok=True)
            raise RuntimeError(
                f"VFT_FETCH_WEIGHTS=1: download of {url} failed ({e}). "
                "On an offline host, unset the flag and drop the file into "
                f"{wd} instead.") from e
        except Exception:
            part.unlink(missing_ok=True)
            raise
        got = h.hexdigest()
        ok = ((kind is None and (recorded is None or got == recorded)) or
              (kind == "sha256" and got == digest) or
              (kind == "sha256-prefix" and got.startswith(digest)))
        if not ok:
            part.unlink(missing_ok=True)
            which = (f"recorded digest (sha256:{recorded})" if kind is None
                     else f"published digest ({kind}:{digest})")
            raise RuntimeError(
                f"{fname}: downloaded file's SHA-256 {got[:16]}... does not "
                f"match the {which}; refusing to use it")
        os.replace(part, dest)  # atomic: never a torn final file
        if kind is None and recorded is None:
            # trust-on-first-use: later fetches verify against this
            record_digest(fname, got)
        verdict = (f" [{kind} verified]" if kind
                   else " [recorded sha256 verified]" if recorded
                   else f" [UNVERIFIED; sha256 {got[:16]}... recorded]")
        print(f"fetched {fname} -> {dest}{verdict}")
        return dest
    return None


def weights_dir() -> Path:
    return Path(os.environ.get(
        "VFT_WEIGHTS_DIR", os.path.expanduser("~/.cache/video_features_tpu")))


# -- weights-identity capture (cache.py feature-cache keying) ----------------
# resolve_params records WHAT it loaded (model key + file sha256, or the
# random-init sentinel) into the thread's active capture list, installed by
# BaseExtractor.__init__ right before the subclass resolves its params. The
# feature cache folds the capture into its key, so a swapped/re-converted
# checkpoint can never serve another checkpoint's cached features.

import threading as _threading

_capture_tls = _threading.local()


def start_weights_capture() -> list:
    """Begin a fresh capture on this thread; returns the (live) list that
    subsequent ``resolve_params`` calls on this thread append to. Each
    call replaces the active list, so sequentially-constructed extractors
    (multi-family runs) each keep only their own resolutions."""
    cap: list = []
    _capture_tls.capture = cap
    return cap


def _record_resolution(rec: dict) -> None:
    cap = getattr(_capture_tls, "capture", None)
    if cap is not None:
        cap.append(rec)


def _file_fingerprint(path: Path) -> str:
    """Streamed sha256 of the resolved checkpoint (memoized through
    cache.file_sha256 so repeated constructions don't re-hash)."""
    from ..cache import file_sha256
    return file_sha256(str(path))


def find_checkpoint(model_key: str,
                    explicit_path: Optional[str] = None) -> Optional[Path]:
    """Locate a weight file for ``model_key`` (msgpack preferred, else torch)."""
    if explicit_path:
        p = Path(explicit_path)
        if not p.exists():
            raise FileNotFoundError(f"weights_path does not exist: {p}")
        return p
    wd = weights_dir()
    for ext in (".msgpack", ".pt", ".pth"):
        p = wd / f"{model_key}{ext}"
        if p.exists():
            return p
    torch_home = Path(os.environ.get("TORCH_HOME",
                                     os.path.expanduser("~/.cache/torch")))
    for fname in HUB_FILENAMES.get(model_key, ()):
        # original upstream filenames are accepted both in the torch hub
        # cache and dropped directly into VFT_WEIGHTS_DIR
        for p in (torch_home / "hub" / "checkpoints" / fname, wd / fname):
            if p.exists():
                return p
    if os.environ.get("VFT_FETCH_WEIGHTS") == "1":
        return fetch_checkpoint(model_key)
    return None


def save_msgpack(params: Any, path: Path) -> None:
    from flax import serialization
    from ..utils.sinks import _write_bytes_atomic
    # a converted checkpoint is a durable artifact other runs will load
    # and fingerprint: a torn write must never be promotable
    _write_bytes_atomic(str(path), serialization.to_bytes(params))


def load_msgpack(template: Any, path: Path) -> Any:
    from flax import serialization
    with open(path, "rb") as f:
        return serialization.from_bytes(template, f.read())


def resolve_params(model_key: str,
                   init_fn: Callable[[], Any],
                   convert_fn: Callable[[Dict[str, Any]], Any],
                   weights_path: Optional[str] = None,
                   allow_random: bool = False,
                   cache_converted: bool = True) -> Any:
    """Return a parameter tree for ``model_key``.

    ``init_fn``: builds a randomly-initialized tree (also the msgpack
    template). ``convert_fn``: maps a torch state_dict onto that tree.
    The whole of it is the start-up ledger's ``params`` phase
    (telemetry/startup.py).
    """
    from ..telemetry import startup
    with startup.phase("params", model_key=model_key):
        return _resolve_params(model_key, init_fn, convert_fn, weights_path,
                               allow_random, cache_converted)


def _resolve_params(model_key: str, init_fn: Callable[[], Any],
                    convert_fn: Callable[[Dict[str, Any]], Any],
                    weights_path: Optional[str], allow_random: bool,
                    cache_converted: bool) -> Any:
    ckpt = find_checkpoint(model_key, weights_path)
    if ckpt is None:
        if allow_random:
            print(f"WARNING: no weights found for {model_key!r}; using RANDOM "
                  "init (allow_random_weights=true). Features will be "
                  "meaningless — for tests/benchmarks only.")
            # seeded init is deterministic: the sentinel keys cache entries
            # for random-weight runs (tests/benches) without a file to hash
            _record_resolution({"model_key": model_key, "random": True})
            return init_fn()
        raise FileNotFoundError(
            f"No weights for {model_key!r}. Provide `weights_path=...`, drop "
            f"a checkpoint into {weights_dir()}, or set "
            "`allow_random_weights=true` for throughput-only runs. Known "
            f"source filenames: {HUB_FILENAMES.get(model_key, '(model-specific)')}")
    try:
        _record_resolution({"model_key": model_key, "path": str(ckpt),
                            "sha256": _file_fingerprint(ckpt)})
    except OSError:
        # capture is keying metadata, not a load requirement; an unreadable
        # stat/hash surfaces as the load failure below if it matters
        pass
    if ckpt.suffix == ".msgpack":
        return load_msgpack(init_fn(), ckpt)
    from .torch_import import load_torch_state_dict
    params = convert_fn(load_torch_state_dict(str(ckpt)))
    if weights_path:
        # an explicit (possibly fine-tuned) checkpoint must not poison the
        # generic {model_key}.msgpack cache used by weights_path-less runs
        cache_converted = False
    if cache_converted:
        out = weights_dir() / f"{model_key}.msgpack"
        try:
            save_msgpack(params, out)
        except OSError:
            pass
    return params
