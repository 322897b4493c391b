"""LFM2-8B-A1B (``model_type`` lfm2_moe) as a feature model over packed rows
of tokens.

A residual stream; every layer is an operator and then a feed-forward block,
each behind an RMSNorm. The operator is, by ``layer_types``, a gated short
convolution (``conv``: ``[B | C | x] = u W_in``, ``C * conv(B * x)``, a
depthwise causal convolution of ``conv_L_cache`` taps, then ``W_out``) or
grouped-query attention (``full_attention``) whose queries and keys are
RMS-normed per head and turned by rotary positions in the ``rotate_half``
layout. The first ``num_dense_layers`` feed-forward blocks are one gated
unit; the others route every token to ``num_experts_per_tok`` of
``num_experts`` experts: a sigmoid over every logit, the experts with the
largest ``sigmoid + expert_bias`` chosen, their un-biased sigmoids divided by
their sum as the gates (``ops/moe.py route``). The equations are in
``reference/lfm2_moe.py``, the plain copy the tests hold this file to.

Positions restart at every segment of a row (``token_rows.
segment_positions``), and the convolution's taps read nothing of another
segment (``ops/ssd.py causal_conv1d``, the taps granite's Mamba layers run).
What this chip holds of a layer is part of the architecture (:class:`Arch`),
as in the other token families: experts ``first_expert`` .. ``first_expert +
experts_held - 1`` of ``num_experts`` and rows ``0`` .. ``vocab_held - 1`` of
the (tied) embedding; the router stays full width. The model ends in its
final RMSNorm.

Weights are made on the device, layer by layer, from the seed
(:func:`layer_weights`, float32, which the reference calls too) and rounded
once to the serving type inside the same program; ``expert_bias`` is drawn
too (:data:`EXPERT_BIAS_STD`): a bias of zeros would leave the selection
rule untested.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import moe, ssd
from ..parallel.sequence import blockwise_attention
from . import token_rows
from .common import scope
from .token_rows import (INIT_STD, part_key, pool_segments, rms_norm,
                         segment_positions)

FAMILY = "LFM2Moe"
#: ``expert_bias`` is normal(0, EXPERT_BIAS_STD) a layer: a trained router's
#: bias is learned, a seeded one of zeros would never move a choice
EXPERT_BIAS_STD = 0.05
OPERATORS = {"conv": "conv", "full_attention": "attn"}


@dataclass(frozen=True)
class Arch:
    """The published ``config.json`` keys the forward pass reads, and this
    chip's share."""
    hidden_size: int
    layer_types: Tuple[str, ...]
    vocab_size: int
    norm_eps: float
    conv_L_cache: int
    num_attention_heads: int
    num_key_value_heads: int
    rope_theta: float
    intermediate_size: int
    moe_intermediate_size: int
    num_dense_layers: int
    num_experts: int
    num_experts_per_tok: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    # -- this chip's share of a layer
    first_expert: int
    experts_held: int
    vocab_held: int

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """``<operator>/<feed-forward>`` of every layer: ``conv`` or
        ``attn``, then ``dense`` or ``moe``."""
        ffn = ["dense" if i < self.num_dense_layers else "moe"
               for i in range(len(self.layer_types))]
        return tuple(f"{OPERATORS[op]}/{f}"
                     for op, f in zip(self.layer_types, ffn))

    @property
    def feature_dim(self) -> int:
        return self.hidden_size

    @property
    def counter_shape(self) -> Tuple[int, int]:
        """(routed layers, the router's width) of a line's counts."""
        return (len(self.layer_types) - self.num_dense_layers,
                self.num_experts)

    @property
    def counter_dim(self) -> int:
        return math.prod(self.counter_shape)


def arch_from_config(published: Mapping[str, Any], layer_shards: int = 1,
                     layer_shard_rank: int = 0) -> Arch:
    """``published`` is the model's ``config.json`` (``configs/
    lfm2_moe.yml``'s ``architecture``), cut in depth by
    ``num_hidden_layers`` (``layer_types`` its first entries); ``layer_shards``
    chips share each layer: each holds ``1 / layer_shards`` of the routed
    experts and of the vocabulary."""
    depth = int(published["num_hidden_layers"])
    types = tuple(published["layer_types"][:depth])
    for what, refused in (
            ("conv_bias", bool(published.get("conv_bias", False))),
            ("use_expert_bias false",
             not published.get("use_expert_bias", True)),
            ("a layer type other than conv and full_attention",
             not set(types) <= set(OPERATORS)),
            ("fewer layer_types than num_hidden_layers", len(types) < depth),
            # the step returns every routed layer's counts beside a feature
            ("a cut that leaves no routed layer",
             depth <= int(published["num_dense_layers"]))):
        if refused:
            raise NotImplementedError(f"lfm2_moe: {what}")
    experts = int(published["num_experts"])
    vocab = int(published["vocab_size"])
    shards, rank = int(layer_shards), int(layer_shard_rank)
    if experts % shards or vocab % shards or not 0 <= rank < shards:
        raise ValueError(f"layer_shards={shards}, layer_shard_rank={rank}: "
                         f"cannot divide {experts} experts and {vocab} "
                         "vocabulary rows")
    # numbers arrive from YAML or a command line: ``1e-05`` as a string
    cast = {"int": int, "float": float, "bool": bool}
    share = {"layer_types", "first_expert", "experts_held", "vocab_held"}
    return Arch(layer_types=types, first_expert=rank * (experts // shards),
                experts_held=experts // shards, vocab_held=vocab // shards,
                **{name: cast[field.type](published[name])
                   for name, field in Arch.__dataclass_fields__.items()
                   if name not in share})


# -- weights -----------------------------------------------------------------

def _normal(key, shape):
    return INIT_STD * jax.random.normal(key, shape, jnp.float32)


def _conv_weights(arch: Arch, key) -> Dict[str, jnp.ndarray]:
    """``in_proj`` ``[B | C | x]``, the depthwise taps (``conv_L_cache``,
    D) by torch's Conv1d default for a fan-in of ``conv_L_cache``, and
    ``out_proj``."""
    k = jax.random.split(key, 3)
    d, taps = arch.hidden_size, arch.conv_L_cache
    bound = 1.0 / math.sqrt(taps)
    return {"in_proj": _normal(k[0], (d, 3 * d)),
            "conv_w": jax.random.uniform(k[1], (taps, d), jnp.float32,
                                         -bound, bound),
            "out_proj": _normal(k[2], (d, d))}


def _attention_weights(arch: Arch, key) -> Dict[str, jnp.ndarray]:
    k = jax.random.split(key, 4)
    d, hd = arch.hidden_size, arch.head_dim
    kv = arch.num_key_value_heads * hd
    shapes = {"q": (d, arch.num_attention_heads * hd), "k": (d, kv),
              "v": (d, kv), "o": (arch.num_attention_heads * hd, d)}
    return {**{name: _normal(k[i], shape)
               for i, (name, shape) in enumerate(shapes.items())},
            "q_norm": jnp.ones((hd,), jnp.float32),
            "k_norm": jnp.ones((hd,), jnp.float32)}


def _draw_layer(arch: Arch, kind: str, key) -> Dict[str, Any]:
    """A gated unit's ``w1`` and ``w3`` are one matrix ``[w1 | w3]``, as
    ``ops/moe.py gated_unit`` takes them."""
    op, ffn = kind.split("/")
    k_op, k_router, k_bias, k_in, k_out, k_experts = jax.random.split(key, 6)
    d = arch.hidden_size
    layer = {"norm_op": jnp.ones((d,), jnp.float32),
             "op": (_conv_weights if op == "conv"
                    else _attention_weights)(arch, k_op),
             "norm_ffn": jnp.ones((d,), jnp.float32)}
    if ffn == "dense":
        i = arch.intermediate_size
        return {**layer, "mlp_in": _normal(k_in, (d, 2 * i)),
                "mlp_out": _normal(k_out, (i, d))}
    i = arch.moe_intermediate_size

    def expert(e):
        e_in, e_out = jax.random.split(jax.random.fold_in(k_experts, e))
        return _normal(e_in, (d, 2 * i)), _normal(e_out, (i, d))

    experts_in, experts_out = jax.vmap(expert)(
        arch.first_expert + jnp.arange(arch.experts_held))
    return {**layer,
            "router": _normal(k_router, (d, arch.num_experts)),
            "expert_bias": EXPERT_BIAS_STD * jax.random.normal(
                k_bias, (arch.num_experts,), jnp.float32),
            "experts_in": experts_in, "experts_out": experts_out}


def _draw(arch: Arch, kind: str, key) -> Dict[str, Any]:
    return token_rows.draw_outer(arch, key) if kind == "outer" \
        else _draw_layer(arch, kind, key)


_draw_float32 = jax.jit(_draw, static_argnums=(0, 1))


def layer_weights(arch: Arch, seed: int, index: int) -> Dict[str, Any]:
    """Layer ``index``'s float32 weights from the seed, this chip's experts
    only: expert ``e`` has its own key, so a chip that holds another share
    draws the same expert. With :func:`outer_weights`, where the program and
    the plain reference both take their weights from."""
    return _draw_float32(arch, arch.layer_kinds[index],
                         part_key(seed, index))


def outer_weights(arch: Arch, seed: int) -> Dict[str, jnp.ndarray]:
    """The held rows of the (tied) embedding and the final norm, float32;
    row ``r`` is the same whatever slice holds it."""
    return _draw_float32(arch, "outer",
                         part_key(seed, len(arch.layer_types)))


def init_params(arch: Arch, seed: int, dtype, sharding=None) -> Dict[str, Any]:
    """The whole tree in ``dtype`` on the device, drawn layer by layer
    (``token_rows.init_params``)."""
    return token_rows.init_params(functools.partial(_draw, arch),
                                  arch.layer_kinds, seed, dtype, sharding)


# -- the forward pass --------------------------------------------------------

def short_conv(arch: Arch, w: Mapping[str, jnp.ndarray], u: jnp.ndarray,
               seg: jnp.ndarray) -> jnp.ndarray:
    """The gated short convolution, causal and within a segment: ``u`` (B,
    T, D) -> (B, T, D) float32."""
    bcx = jnp.dot(u, w["in_proj"], preferred_element_type=jnp.float32
                  ).astype(u.dtype)
    with scope("taps"):
        b, c, x = jnp.split(bcx, 3, axis=-1)
        y = c * ssd.causal_conv1d(b * x, w["conv_w"], None, seg)
    return jnp.dot(y, w["out_proj"], preferred_element_type=jnp.float32)


def rotary_tables(arch: Arch, seg: jnp.ndarray
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos and sin (B, T, head_dim / 2) float32 of every token's position
    within its segment, ``theta^(-2i / head_dim)`` over the whole head."""
    hd = arch.head_dim
    inv_freq = arch.rope_theta ** (-np.arange(0, hd, 2, dtype=np.float64)
                                   / hd)
    angles = segment_positions(seg).astype(jnp.float32)[..., None] \
        * jnp.asarray(inv_freq, jnp.float32)
    return jnp.cos(angles), jnp.sin(angles)


def rotate(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """The ``rotate_half`` layout: channel ``j`` turns with channel ``j +
    d / 2``, ``x cos + rotate_half(x) sin`` with ``rotate_half(x) = [-x2 |
    x1]``, float32 inside. ``cos`` / ``sin`` broadcast against (..., d /
    2)."""
    x32 = x.astype(jnp.float32)
    first, second = jnp.split(x32, 2, axis=-1)
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin],
                           axis=-1).astype(x.dtype)


def attention(arch: Arch, w: Mapping[str, jnp.ndarray], u: jnp.ndarray,
              seg: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray
              ) -> jnp.ndarray:
    """Grouped-query attention with per-head RMS-normed queries and keys,
    causal and within a segment: ``u`` (B, T, D) -> (B, T, D) float32."""
    bsz, t, _ = u.shape
    heads, kv, hd = (arch.num_attention_heads, arch.num_key_value_heads,
                     arch.head_dim)

    def project(name, n):
        return jnp.dot(u, w[name], preferred_element_type=jnp.float32
                       ).astype(u.dtype).reshape(bsz, t, n, hd)

    q = rms_norm(project("q", heads), w["q_norm"], arch.norm_eps)
    k = rms_norm(project("k", kv), w["k_norm"], arch.norm_eps)
    v = project("v", kv)
    with scope("rope"):
        q = rotate(q, cos[:, :, None], sin[:, :, None])
        k = rotate(k, cos[:, :, None], sin[:, :, None])
    k, v = (jnp.repeat(a, heads // kv, axis=2) for a in (k, v))
    with scope("core"):
        out = blockwise_attention(q, k, v, causal=True, segment_ids=seg,
                                  scale=hd ** -0.5)
    return jnp.dot(out.reshape(bsz, t, heads * hd), w["o"],
                   preferred_element_type=jnp.float32)


def token_states(arch: Arch, params: Mapping[str, Any], rows: jnp.ndarray,
                 dtype, router_dtype=jnp.float32
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``rows`` (B, 2, T) int32 -> the final hidden states ``f`` (B, T, D)
    float32 (after the last RMSNorm) and every routed layer's choices
    (routed layers, B, T, K)."""
    ids, seg = rows[:, 0], rows[:, 1]
    bsz, t = ids.shape
    valid = (seg > 0).reshape(-1)
    eps = arch.norm_eps
    with scope(FAMILY, "embed"):
        x = jnp.take(params["embed"], ids, axis=0).astype(dtype)
    with scope(FAMILY, "attn", "rope"):
        cos, sin = rotary_tables(arch, seg)

    def add(x, out):
        return (x.astype(jnp.float32) + out.reshape(bsz, t, -1)).astype(dtype)

    chosen = []
    for kind, w in zip(arch.layer_kinds, params["layers"]):
        op, ffn = kind.split("/")
        with scope(FAMILY, "short_conv" if op == "conv" else "attn"):
            u = rms_norm(x, w["norm_op"], eps)
            x = add(x, short_conv(arch, w["op"], u, seg) if op == "conv"
                    else attention(arch, w["op"], u, seg, cos, sin))
        if ffn == "dense":
            with scope(FAMILY, "dense_mlp"):
                u = rms_norm(x, w["norm_ffn"], eps)
                x = add(x, moe.gated_unit(u, w["mlp_in"], w["mlp_out"]))
            continue
        with scope(FAMILY, "moe"):
            u = rms_norm(x, w["norm_ffn"], eps).reshape(bsz * t, -1)
            gates, picks = moe.route(
                u, w["router"], arch.num_experts_per_tok, router_dtype,
                rule="sigmoid", selection_bias=w["expert_bias"],
                renormalise=arch.norm_topk_prob,
                scaling=arch.routed_scaling_factor)
            x = add(x, moe.held_experts(u, gates, picks, w["experts_in"],
                                        w["experts_out"], arch.first_expert,
                                        valid, arch.num_experts))
        chosen.append(picks.reshape(bsz, t, -1))
    with scope(FAMILY, "pool"):
        f = rms_norm(x.astype(jnp.float32), params["final_norm"], eps)
    return f, jnp.stack(chosen)


def segment_features(arch: Arch, max_segments: int, dtype,
                     params: Mapping[str, Any], rows: jnp.ndarray
                     ) -> jnp.ndarray:
    """The device step: ``rows`` (B, 2, T) -> one line per segment
    (``token_rows.pool_segments``); per-token states never leave it."""
    f, chosen = token_states(arch, params, rows, dtype)
    return pool_segments(FAMILY, arch.num_experts, max_segments, rows[:, 1],
                         f, chosen)
