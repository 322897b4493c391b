"""TPU kernels for the framework's hot ops.

The reference ships exactly one native compute kernel — the PWC-Net
correlation (cost volume) written in raw CUDA C and JIT-compiled through CuPy
(reference models/pwc/pwc_src/correlation.py:47-115) — and does its other
memory-bound hot loop, the RAFT correlation-pyramid lookup, as a
grid_sample gather (reference models/raft/raft_src/corr.py:29-50). Here:

  - :mod:`corr_lookup` — the windowed bilinear pyramid lookup recast as
    one-hot matmul contractions (gather-free, rides the MXU), as a fused
    Pallas kernel and a pure-XLA twin. Selected by the
    ``corr_lookup_impl`` config key (models/raft.py
    configure_corr_lookup, applied at extractor init; the
    ``VFT_CORR_LOOKUP`` env var is the trace-time override) —
    ``pallas`` (TPU default, the 20x one) | ``onehot`` | ``gather``
    (CPU default).
  - :mod:`cost_volume` — the 81-channel windowed cost volume as the XLA
    shifted-window formulation. A Pallas twin was built, hardware-
    validated, measured TIED with XLA across every real PWC shape in f32
    and bf16, and deleted in round 5 (measured negative result recorded
    in that module's docstring).

Measured on a TPU v5e before PR 0, with a D2H-fenced timer
(parallel/mesh.py settle), on an installation that no longer exists — a
claim to re-measure, not a current number:

  corr lookup, end-to-end 20-iteration RAFT forward (16 pairs @224px):
    gather 4,097 ms / one-hot 331 ms / fused Pallas 200 ms. The 81-tap
    4-corner scalar gathers are the worst access pattern the TPU has; the
    MXU contraction forms win by 12-20x, so Pallas is the TPU default.
"""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """Pallas TPU kernels run in interpreter mode off-TPU (tests on CPU)."""
    return jax.default_backend() != "tpu"


from .cost_volume import cost_volume  # noqa: E402
from .corr_lookup import corr_lookup_onehot, corr_lookup_pallas  # noqa: E402

__all__ = [
    "interpret_mode",
    "cost_volume", "corr_lookup_onehot", "corr_lookup_pallas",
]
