"""The chip check. A run that finds no accelerator, too few chips, or a
device the peaks table does not know fails here and prints no result."""
from __future__ import annotations

from pathlib import Path
from typing import Any, Dict

from .manifest import read_json


class NoChip(RuntimeError):
    """The cell cannot be measured on this machine."""


def require_chip(chips: int, peaks_file: Path) -> Dict[str, Any]:
    """The devices the cell runs on, as JAX reports them, with the published
    peaks of their kind. Raises :class:`NoChip` instead of measuring anything
    on another platform."""
    import jax
    platform = jax.default_backend()
    devices = jax.local_devices()
    if platform != "tpu":
        raise NoChip(f"JAX's default backend is {platform!r} (devices: "
                     f"{devices}); this benchmark measures on a TPU only")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chip(s), JAX found "
                     f"{len(devices)}: {devices}")
    kind = devices[0].device_kind
    table = read_json(peaks_file)
    if kind not in table or not isinstance(table[kind], dict):
        raise NoChip(f"device_kind {kind!r} is not in {peaks_file.name}; "
                     "add its published peaks with their source")
    return {"platform": platform, "kind": kind, "count": len(devices),
            "devices": chips_of(chips), "peaks": table[kind]}


def chips_of(chips: int):
    """The devices a cell of ``chips`` chips runs on."""
    import jax
    return jax.local_devices()[:chips]


def memory_peak_bytes(devices) -> int:
    """Peak bytes held on the fullest chip since the process started, as the
    backend reports them: the allocator's ``peak_bytes_in_use`` (parameters,
    inputs, outputs) plus ``peak_bytes_reserved``, where this backend books
    the temporaries of the largest program it ran (1,648,410,624 B after the
    128-clip r21d program, which is its ``memory_analysis()`` ``temp_size``
    and which ``peak_bytes_in_use`` alone does not show; PERF.md, section
    4). A lifetime peak: warm-up counts, so the harness reads it as
    the window opens and again after it and prints both."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks) if peaks else 0
