"""TPU kernels for the framework's hot ops.

The reference ships exactly one native compute kernel — the PWC-Net
correlation (cost volume) written in raw CUDA C and JIT-compiled through CuPy
(reference models/pwc/pwc_src/correlation.py:47-115) — and does its other
memory-bound hot loop, the RAFT correlation-pyramid lookup, as a
grid_sample gather (reference models/raft/raft_src/corr.py:29-50). Here:

  - :mod:`corr_lookup` — the windowed bilinear pyramid lookup recast as
    one-hot matmul contractions (gather-free, rides the MXU): two Pallas
    kernels (``proj``, fused with the motion encoder's convc1, and
    ``level``) and a pure-XLA twin (``onehot``), beside the reference's
    ``gather``. ``corr_lookup.prepare_lookup`` picks the form from the
    backend and the plane's size; nothing else selects one.
  - :mod:`cost_volume` — the 81-channel windowed cost volume as the XLA
    shifted-window formulation. A Pallas twin was built, hardware-
    validated, measured TIED with XLA across every real PWC shape in f32
    and bf16, and deleted in round 5 (measured negative result recorded
    in that module's docstring).
  - :mod:`grouped_matmul` — the routed experts' grouped products
    (``ops/moe.py held_experts``, both token families) as one Pallas kernel
    tiled by group and by the expert's width, where XLA's own lowering of
    ``jax.lax.ragged_dot`` reads 38% and 55% of the MXU's peak.
    ``grouped_matmul_supported`` says from the backend and the operands'
    shapes whether it runs; nothing else selects it.

Importing the package imports no Pallas (a second of start-up): each
kernel's module imports it where it is used.
"""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """Pallas TPU kernels run in interpreter mode off-TPU (tests on CPU)."""
    return jax.default_backend() != "tpu"


__all__ = ["interpret_mode"]
