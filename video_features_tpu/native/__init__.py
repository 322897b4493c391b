"""Native (C++) runtime IO: build-on-demand, ctypes-bound, always optional.

``vft_native.cpp`` is compiled with g++ into a cached shared library on first
use (no pybind11 in this environment — plain ``extern "C"`` + ctypes); the
.so lives under the in-checkout ``.cache/native`` (config.CACHE_ROOT).
Every entry point has a pure-Python fallback at its call site, so the
framework runs unchanged where a toolchain is unavailable; set
``VFT_NATIVE=0`` to force the fallbacks.

Exports:
  available()               -> bool
  status()                  -> 'native' | 'python' | 'unused', no build
  write_npy_atomic(path, a) -> write a .npy via temp-file + fsync + rename
  validate_npy(path)        -> structural corruption check, O(header)
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from pathlib import Path
from typing import Optional

import numpy as np

_SRC = Path(__file__).with_name("vft_native.cpp")
_lib: Optional[ctypes.CDLL] = None
_build_failed = False


def _cache_dir() -> Path:
    from ..config import CACHE_ROOT
    d = Path(os.environ.get("VFT_CACHE_DIR") or CACHE_ROOT) / "native"
    d.mkdir(parents=True, exist_ok=True)
    return d


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed
    if _lib is not None or _build_failed:
        return _lib
    if os.environ.get("VFT_NATIVE", "").strip() == "0":
        _build_failed = True
        return None
    try:
        src = _SRC.read_bytes()
        tag = hashlib.sha1(src).hexdigest()[:16]
        so = _cache_dir() / f"vft_native-{tag}.so"
        if not so.exists():
            # build into a temp name then rename: parallel workers racing to
            # build get a whole file or none
            with tempfile.NamedTemporaryFile(
                    dir=so.parent, suffix=".so", delete=False) as tmp:
                tmp_path = tmp.name
            try:
                cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
                       str(_SRC), "-o", tmp_path]
                subprocess.run(cmd, check=True, capture_output=True)
                os.replace(tmp_path, so)
            except BaseException:
                try:
                    os.unlink(tmp_path)
                except OSError:
                    pass
                raise
        lib = ctypes.CDLL(str(so))
        lib.vft_write_npy.restype = ctypes.c_int
        lib.vft_write_npy.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
            ctypes.c_void_p, ctypes.c_int64]
        lib.vft_validate_npy.restype = ctypes.c_int
        lib.vft_validate_npy.argtypes = [ctypes.c_char_p]
        _lib = lib
    except Exception:
        _build_failed = True
        _lib = None
    return _lib


def available() -> bool:
    return _load() is not None


def status() -> str:
    """Which .npy writer this process used so far, without triggering a
    build: ``native`` (the g++-built library loaded), ``python`` (build or
    load failed, or ``VFT_NATIVE=0`` — the byte-identical fallback ran) or
    ``unused`` (nothing was written). The run manifest records it."""
    if _lib is not None:
        return "native"
    return "python" if _build_failed else "unused"


def write_npy_atomic(fpath: str, value) -> bool:
    """Write ``value`` as .npy with atomic replace. Returns False when the
    native path cannot handle it (object/structured dtype, or the library
    is unavailable) — callers fall back to np.save. Non-contiguous inputs
    are copied to C order first."""
    lib = _load()
    if lib is None:
        return False
    arr = np.asanyarray(value)
    if arr.dtype.hasobject or arr.dtype.fields is not None:
        return False
    # np.save appends '.npy' when missing — preserve that contract
    if not str(fpath).endswith(".npy"):
        fpath = str(fpath) + ".npy"
    if not arr.flags.c_contiguous:
        # NOT ascontiguousarray unconditionally: it promotes 0-d to (1,)
        arr = np.ascontiguousarray(arr)
    shape = (ctypes.c_int64 * max(arr.ndim, 1))(*arr.shape)
    rc = lib.vft_write_npy(
        str(fpath).encode(), arr.dtype.str.encode(), shape, arr.ndim,
        arr.ctypes.data_as(ctypes.c_void_p), ctypes.c_int64(arr.nbytes))
    if rc != 0:
        raise OSError(f"vft_write_npy({fpath}) failed: {rc} "
                      f"({os.strerror(-rc) if -rc < 1000 else 'format'})")
    return True


def validate_npy(fpath: str) -> Optional[bool]:
    """True = structurally valid, False = corrupt/truncated, None = cannot
    judge natively (no lib, exotic header) — caller should np.load."""
    lib = _load()
    if lib is None:
        return None
    rc = lib.vft_validate_npy(str(fpath).encode())
    if rc == 0:
        return True
    if rc in (-1000, -1001):  # VFT_EFORMAT, VFT_ETRUNCATED
        return False
    return None  # header we don't parse, or OS error: let np.load decide
