"""What the models over packed rows of tokens share (``granite_hybrid``,
``deepseek_v2``, ``lfm2_moe``): how their seeded weights are keyed and
rounded, the RMSNorm, and the step's last stage, which turns per-token
states into one line per segment.

A row is ``(2, T) int32``: token ids and segment ids (``parallel/packer.py
SegmentPacker``; 0 is padding, a document's window is one segment, each a
contiguous run).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Sequence

import jax
import jax.numpy as jnp

from ..telemetry import startup
from .common import scope

#: matrices are normal(0, INIT_STD): no checkpoint exists in a sealed machine
INIT_STD = 0.02


def part_key(seed: int, index: int):
    """The key of layer ``index`` (the outer tree takes the index behind the
    last layer's)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), index)


def serving_tree(weights: Any, dtype) -> Any:
    """Matrices rounded once to ``dtype``; the per-channel vectors (norms,
    ``A_log``, ``dt_bias``, ``D``, the convolution's bias) stay float32, as
    a checkpoint keeps them: ``dt_bias`` near -7 in bfloat16 would move a
    head's step by 3%."""
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype if x.ndim >= 2 else jnp.float32), weights)


def draw_outer(arch, key) -> Dict[str, jnp.ndarray]:
    """The held rows of the embedding, each under its own key (row ``r`` is
    the same whatever slice of the vocabulary holds it), and the final norm,
    float32."""
    rows = jax.vmap(lambda r: INIT_STD * jax.random.normal(
        jax.random.fold_in(key, r), (arch.hidden_size,), jnp.float32))
    return {"embed": rows(jnp.arange(arch.vocab_held)),
            "final_norm": jnp.ones((arch.hidden_size,), jnp.float32)}


def init_params(draw: Callable[[str, Any], Dict[str, Any]],
                kinds: Sequence[str], seed: int, dtype, sharding=None
                ) -> Dict[str, Any]:
    """The whole tree in ``dtype`` on the device: each part is drawn in
    float32 (``draw(kind, key)``; ``"outer"`` or a layer's kind) and rounded
    once inside one program, so no float32 copy of a layer is ever held. One
    program per kind of layer: the key is traced."""
    @functools.partial(jax.jit, static_argnums=0, out_shardings=sharding)
    def rounded(kind, key):
        return serving_tree(draw(kind, key), dtype)

    # the host side of it: the draws run on the device after this returns
    with startup.phase("params", kinds=",".join(sorted(set(kinds)))):
        return {**rounded("outer", part_key(seed, len(kinds))),
                "layers": [rounded(kind, part_key(seed, i))
                           for i, kind in enumerate(kinds)]}


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float,
             axis: int = -1) -> jnp.ndarray:
    """Over the channels on ``axis``: float32 inside, ``x.dtype`` out.
    ``weight`` spans ``axis`` and the axes before it that it has."""
    axis %= x.ndim
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=axis, keepdims=True)
                          + eps)
    weight = weight.astype(jnp.float32).reshape(
        weight.shape + (1,) * (x.ndim - 1 - axis))
    return (x32 * scale * weight).astype(x.dtype)


def segment_positions(seg: jnp.ndarray) -> jnp.ndarray:
    """``seg`` (B, T) -> every token's index within its segment (B, T)
    int32: its index in the row less the index of its segment's first
    token. Padding counts on from wherever it starts; nothing reads it."""
    index = jnp.arange(seg.shape[1], dtype=jnp.int32)[None, :]
    first = jnp.concatenate(
        [jnp.ones_like(seg[:, :1], bool), seg[:, 1:] != seg[:, :-1]], axis=1)
    return index - jax.lax.cummax(jnp.where(first, index, 0), axis=1)


def pool_segments(family: str, experts: int, max_segments: int,
                  seg: jnp.ndarray, f: jnp.ndarray, chosen: jnp.ndarray
                  ) -> jnp.ndarray:
    """``f`` (B, T, D) and ``chosen`` (layers, B, T, K) -> (B, max_segments,
    D + layers * experts) float32. Line ``s - 1`` of a row is segment ``s``:
    the mean of ``f`` over its tokens, then for every routed layer the
    number of its tokens sent to each of the router's ``experts``. Padding
    (segment 0) is in no line; a segment id the row does not hold gives a
    line of zeros."""
    with scope(family, "pool"):
        member = (seg[:, None, :] == jnp.arange(
            1, max_segments + 1)[None, :, None]).astype(jnp.float32)
        tokens = member.sum(axis=-1, keepdims=True)            # (B, S, 1)
        pooled = jnp.einsum("bst,btd->bsd", member, f,
                            precision=jax.lax.Precision.HIGHEST) \
            / jnp.maximum(tokens, 1.0)
        # (layers, B, T, K) -> how often each expert was chosen per token
        picked = jax.nn.one_hot(chosen, experts,
                                dtype=jnp.float32).sum(axis=3)
        counts = jnp.einsum("bst,lbte->bsle", member, picked,
                            precision=jax.lax.Precision.HIGHEST)
        return jnp.concatenate(
            [pooled, counts.reshape(*counts.shape[:2], -1)], axis=-1)
