"""Shared Flax building blocks for the inference-only model zoo.

All models here run in NHWC / NDHWC (channels-last) — the layout the TPU's
MXU and XLA's conv tiling want — with weights transplanted from the
reference's NCHW torch checkpoints via `weights/torch_import.py`.

BatchNorm is the inference affine form: every family in the reference runs
under `torch.no_grad()` with `.eval()` (reference models/_base/base_extractor.py),
so running statistics are constants; XLA folds the multiply/add into the
adjacent conv epilogue.
"""
from __future__ import annotations

import contextlib
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn


def scope(*names: str) -> contextlib.ExitStack:
    """``with scope("RAFT", "ingest"):`` — nested ``jax.named_scope``s, for
    the operations a device step runs OUTSIDE its flax module (the module's
    own calls already sit under ``<Module>/<submodule>/...``). Metadata
    only: a profiler trace then names every operation of the program
    ``<Module>/<stage>/...`` (PERF.md section 3), the compiled program is
    the same."""
    stack = contextlib.ExitStack()
    for name in names:
        stack.enter_context(jax.named_scope(name))
    return stack


class BNInf(nn.Module):
    """Inference-mode batchnorm: ``(x - mean) / sqrt(var + eps) * scale + bias``."""
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        c = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (c,))
        bias = self.param("bias", nn.initializers.zeros, (c,))
        mean = self.param("mean", nn.initializers.zeros, (c,))
        var = self.param("var", nn.initializers.ones, (c,))
        inv = jax.lax.rsqrt(var.astype(jnp.float32) + self.eps).astype(x.dtype)
        scale = scale.astype(x.dtype) * inv
        return x * scale + (bias.astype(x.dtype) - mean.astype(x.dtype) * scale)


def max_pool_same_torch(x: jnp.ndarray, window: Sequence[int],
                        strides: Sequence[int],
                        padding: Sequence[Tuple[int, int]]) -> jnp.ndarray:
    """Max pool over the middle (spatial) axes of an N...C tensor.

    Padding value is -inf, i.e. padded cells never win — same as torch
    MaxPool2d/3d with implicit padding.
    """
    dims = (1, *window, 1)
    strides_ = (1, *strides, 1)
    pad = ((0, 0), *padding, (0, 0))
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, dims, strides_, pad)
