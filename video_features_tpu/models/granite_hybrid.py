"""granite-4.0-h-small (``model_type`` granitemoehybrid) as a feature model
over packed rows of tokens.

A residual stream with ``embedding_multiplier`` and ``residual_multiplier``;
every layer is a mixer (Mamba-2, or grouped-query attention without any
position embedding) and then a block of routed experts beside a shared
expert, each behind an RMSNorm. The equations are in
``reference/granite_hybrid.py``, the plain copy the tests hold this file to.

What this chip holds of a layer is part of the architecture it is given
(:class:`Arch`): experts ``first_expert`` .. ``first_expert + experts_held -
1`` of ``num_local_experts`` and rows ``0`` .. ``vocab_held - 1`` of the
vocabulary, the share of one of the chips that divide a layer among them
(``ops/moe.py``). The router stays ``num_local_experts`` wide.

A row is ``(2, T) int32``: token ids and segment ids (``parallel/packer.py
SegmentPacker``; 0 is padding, a document's window is one segment). The step
returns, per row, one line per segment: the mean of the final hidden states
over the segment's tokens and, behind it, how many of its tokens each layer's
router sent to each expert. Per-token states never leave the device.

Weights are made on the device, layer by layer, from the seed
(:func:`layer_weights`, float32, which the reference calls too) and rounded
once to the serving type inside the same program: the float32 tree of the
benchmark's configuration would be 19 GB.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, Mapping, Tuple

import jax
import jax.numpy as jnp

from ..ops import moe, ssd
from ..parallel.sequence import blockwise_attention
from . import token_rows
from .common import scope
from .token_rows import INIT_STD, rms_norm
from .token_rows import part_key as _part_key


@dataclass(frozen=True)
class Arch:
    """The published ``config.json`` keys the forward pass reads, and this
    chip's share."""
    hidden_size: int
    layer_types: Tuple[str, ...]
    vocab_size: int
    embedding_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    rms_norm_eps: float
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    mamba_d_conv: int
    mamba_n_groups: int
    mamba_chunk_size: int
    num_attention_heads: int
    num_key_value_heads: int
    attention_multiplier: float
    num_local_experts: int
    num_experts_per_tok: int
    intermediate_size: int
    shared_intermediate_size: int
    # -- this chip's share of a layer
    first_expert: int
    experts_held: int
    vocab_held: int

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.mamba_d_inner + \
            2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def feature_dim(self) -> int:
        return self.hidden_size

    @property
    def counter_shape(self) -> Tuple[int, int]:
        """(routed layers, the router's width) of a line's counts."""
        return len(self.layer_types), self.num_local_experts

    @property
    def counter_dim(self) -> int:
        return math.prod(self.counter_shape)


def arch_from_config(published: Mapping[str, Any], layer_shards: int = 1,
                     layer_shard_rank: int = 0) -> Arch:
    """``published`` is the model's ``config.json`` (``configs/
    granite_hybrid.yml``'s ``architecture``), cut in depth by
    ``num_hidden_layers``; ``layer_shards`` chips share each layer: each
    holds ``1 / layer_shards`` of the routed experts and of the vocabulary."""
    if published["mamba_n_groups"] != 1:
        raise NotImplementedError("mamba_n_groups > 1")
    if published.get("position_embedding_type", "nope") != "nope":
        raise NotImplementedError("a position embedding in the attention "
                                  "layers")
    depth = int(published["num_hidden_layers"])
    experts, vocab = (int(published["num_local_experts"]),
                      int(published["vocab_size"]))
    shards, rank = int(layer_shards), int(layer_shard_rank)
    if experts % shards or vocab % shards or not 0 <= rank < shards:
        raise ValueError(f"layer_shards={shards}, layer_shard_rank={rank}: "
                         f"cannot divide {experts} experts and {vocab} "
                         "vocabulary rows")
    # numbers arrive from YAML or a command line: ``1e-05`` as a string
    cast = {"int": int, "float": float}
    share = {"layer_types", "first_expert", "experts_held", "vocab_held"}
    return Arch(layer_types=tuple(published["layer_types"][:depth]),
                first_expert=rank * (experts // shards),
                experts_held=experts // shards, vocab_held=vocab // shards,
                **{name: cast[field.type](published[name])
                   for name, field in Arch.__dataclass_fields__.items()
                   if name not in share})


# -- weights -------------------------------------------------------------------

def _mamba_weights(arch: Arch, key) -> Dict[str, jnp.ndarray]:
    """``A_log``, ``dt_bias`` and ``D`` by the Mamba-2 release's
    initialisation (A uniform in [1, 16], dt log-uniform in [0.001, 0.1]
    through the inverse softplus, D ones); the convolution by torch's
    Conv1d default for a fan-in of ``mamba_d_conv``."""
    k = jax.random.split(key, 6)
    h, d_in = arch.mamba_n_heads, arch.mamba_d_inner
    proj = 2 * d_in + 2 * arch.mamba_n_groups * arch.mamba_d_state + h
    dt = jnp.exp(jax.random.uniform(k[2], (h,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    bound = 1.0 / math.sqrt(arch.mamba_d_conv)
    return {
        "in_proj": INIT_STD * jax.random.normal(
            k[0], (arch.hidden_size, proj), jnp.float32),
        "conv_w": jax.random.uniform(k[1], (arch.mamba_d_conv, arch.conv_dim),
                                     jnp.float32, -bound, bound),
        "conv_b": jax.random.uniform(k[5], (arch.conv_dim,), jnp.float32,
                                     -bound, bound),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(k[3], (h,), jnp.float32,
                                            1.0, 16.0)),
        "D": jnp.ones((h,), jnp.float32),
        "norm": jnp.ones((d_in,), jnp.float32),
        "out_proj": INIT_STD * jax.random.normal(
            k[4], (d_in, arch.hidden_size), jnp.float32),
    }


def _attention_weights(arch: Arch, key) -> Dict[str, jnp.ndarray]:
    k = jax.random.split(key, 4)
    d, kv = arch.hidden_size, arch.num_key_value_heads * arch.head_dim
    shapes = {"q": (d, d), "k": (d, kv), "v": (d, kv), "o": (d, d)}
    return {name: INIT_STD * jax.random.normal(k[i], shape, jnp.float32)
            for i, (name, shape) in enumerate(shapes.items())}


def _draw_layer(arch: Arch, kind: str, key) -> Dict[str, Any]:
    k_mixer, k_router, k_shared_in, k_shared_out, k_experts = \
        jax.random.split(key, 5)
    d, i, s = (arch.hidden_size, arch.intermediate_size,
               arch.shared_intermediate_size)

    def expert(e):
        k_in, k_out = jax.random.split(jax.random.fold_in(k_experts, e))
        return (INIT_STD * jax.random.normal(k_in, (d, 2 * i), jnp.float32),
                INIT_STD * jax.random.normal(k_out, (i, d), jnp.float32))

    experts_in, experts_out = jax.vmap(expert)(
        arch.first_expert + jnp.arange(arch.experts_held))
    return {
        "norm1": jnp.ones((d,), jnp.float32),
        "mixer": (_mamba_weights if kind == "mamba"
                  else _attention_weights)(arch, k_mixer),
        "norm2": jnp.ones((d,), jnp.float32),
        "router": INIT_STD * jax.random.normal(
            k_router, (d, arch.num_local_experts), jnp.float32),
        "experts_in": experts_in, "experts_out": experts_out,
        "shared_in": INIT_STD * jax.random.normal(
            k_shared_in, (d, 2 * s), jnp.float32),
        "shared_out": INIT_STD * jax.random.normal(
            k_shared_out, (s, d), jnp.float32),
    }


def _draw(arch: Arch, kind: str, key) -> Dict[str, Any]:
    return token_rows.draw_outer(arch, key) if kind == "outer" \
        else _draw_layer(arch, kind, key)


_draw_float32 = jax.jit(_draw, static_argnums=(0, 1))


def layer_weights(arch: Arch, seed: int, index: int) -> Dict[str, Any]:
    """Layer ``index``'s float32 weights from the seed, this chip's experts
    only: expert ``e`` has its own key, so a chip that holds another share
    draws the same expert. With :func:`outer_weights`, where the program and
    the plain reference both take their weights from."""
    return _draw_float32(arch, arch.layer_types[index],
                         _part_key(seed, index))


def outer_weights(arch: Arch, seed: int) -> Dict[str, jnp.ndarray]:
    """The held rows of the (tied) embedding and the final norm, float32;
    row ``r`` is the same whatever slice holds it."""
    return _draw_float32(arch, "outer",
                         _part_key(seed, len(arch.layer_types)))


def init_params(arch: Arch, seed: int, dtype, sharding=None) -> Dict[str, Any]:
    """The whole tree in ``dtype`` on the device, drawn layer by layer
    (``token_rows.init_params``)."""
    return token_rows.init_params(functools.partial(_draw, arch),
                                  arch.layer_types, seed, dtype, sharding)


# -- the forward pass ------------------------------------------------------------

def mamba_mixer(arch: Arch, w: Mapping[str, jnp.ndarray], u: jnp.ndarray,
                seg: jnp.ndarray, state_dtype=jnp.float32) -> jnp.ndarray:
    """``u`` (B, T, D) -> (B, T, D) float32. Between the projections every
    activation is in the scan's layout, (B, C, channels, Q) (``ops/ssd.py``),
    and the projection out reads the normed output as it is."""
    bsz, t, _ = u.shape
    h, p, n = arch.mamba_n_heads, arch.mamba_d_head, arch.mamba_d_state
    d_in, q = arch.mamba_d_inner, arch.mamba_chunk_size
    z, xbc, dt, seg = ssd.mamba_in(w, u, seg, d_in, arch.conv_dim, q)
    nc = seg.shape[1]
    xs = xbc[:, :, :d_in].reshape(bsz, nc, h, p, q)
    b, c = xbc[:, :, d_in:d_in + n], xbc[:, :, d_in + n:]
    with scope("ssd"):
        y = ssd.ssd_chunks(xs, dt, -jnp.exp(w["A_log"].astype(jnp.float32)),
                           b, c, seg, state_dtype)
    y = y + xs.astype(jnp.float32) * w["D"].astype(jnp.float32)[:, None, None]
    y = y.reshape(bsz, nc, d_in, q) * jax.nn.silu(z)
    y = rms_norm(y, w["norm"], arch.rms_norm_eps, axis=2).astype(u.dtype)
    out = jnp.einsum("bckq,kd->bcqd", y, w["out_proj"],
                     preferred_element_type=jnp.float32)
    return out.reshape(bsz, nc * q, -1)[:, :t]


def attention_mixer(arch: Arch, w: Mapping[str, jnp.ndarray], u: jnp.ndarray,
                    seg: jnp.ndarray) -> jnp.ndarray:
    """Grouped-query attention, causal and within a segment, no position
    embedding: ``u`` (B, T, D) -> (B, T, D) float32."""
    bsz, t, _ = u.shape
    heads, kv, hd = (arch.num_attention_heads, arch.num_key_value_heads,
                     arch.head_dim)

    def project(name, n):
        return jnp.dot(u, w[name], preferred_element_type=jnp.float32
                       ).astype(u.dtype).reshape(bsz, t, n, hd)

    q, k, v = project("q", heads), project("k", kv), project("v", kv)
    k, v = (jnp.repeat(a, heads // kv, axis=2) for a in (k, v))
    out = blockwise_attention(q, k, v, causal=True, segment_ids=seg,
                              scale=arch.attention_multiplier)
    return jnp.dot(out.reshape(bsz, t, heads * hd), w["o"],
                   preferred_element_type=jnp.float32)


def token_states(arch: Arch, params: Mapping[str, Any], rows: jnp.ndarray,
                 dtype, state_dtype=jnp.float32, router_dtype=jnp.float32
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``rows`` (B, 2, T) int32 -> the final hidden states ``f`` (B, T, D)
    float32 (after the last RMSNorm) and every layer's router choices
    (layers, B, T, K)."""
    ids, seg = rows[:, 0], rows[:, 1]
    bsz, t = ids.shape
    valid = (seg > 0).reshape(-1)
    with scope("GraniteHybrid", "embed"):
        x = (arch.embedding_multiplier
             * jnp.take(params["embed"], ids, axis=0).astype(jnp.float32)
             ).astype(dtype)
    chosen = []
    for kind, w in zip(arch.layer_types, params["layers"]):
        with scope("GraniteHybrid", "mamba" if kind == "mamba" else "attn"):
            u = rms_norm(x, w["norm1"], arch.rms_norm_eps)
            mixed = (mamba_mixer(arch, w["mixer"], u, seg, state_dtype)
                     if kind == "mamba"
                     else attention_mixer(arch, w["mixer"], u, seg))
            x = (x.astype(jnp.float32)
                 + arch.residual_multiplier * mixed).astype(dtype)
        with scope("GraniteHybrid", "moe"):
            u = rms_norm(x, w["norm2"], arch.rms_norm_eps
                         ).reshape(bsz * t, -1)
            gates, picks = moe.route(u, w["router"],
                                     arch.num_experts_per_tok, router_dtype)
            routed = moe.held_experts(u, gates, picks, w["experts_in"],
                                      w["experts_out"], arch.first_expert,
                                      valid, arch.num_local_experts)
        with scope("GraniteHybrid", "shared_mlp"):
            out = routed + moe.gated_unit(u, w["shared_in"], w["shared_out"])
            x = (x.astype(jnp.float32) + arch.residual_multiplier
                 * out.reshape(bsz, t, -1)).astype(dtype)
        chosen.append(picks.reshape(bsz, t, -1))
    with scope("GraniteHybrid", "pool"):
        f = rms_norm(x.astype(jnp.float32), params["final_norm"],
                     arch.rms_norm_eps)
    return f, jnp.stack(chosen)


def pool_segments(arch: Arch, max_segments: int, seg: jnp.ndarray,
                  f: jnp.ndarray, chosen: jnp.ndarray) -> jnp.ndarray:
    """``f`` (B, T, D) and ``chosen`` (layers, B, T, K) -> (B, max_segments,
    feature_dim + counter_dim) float32 (``token_rows.pool_segments``)."""
    return token_rows.pool_segments("GraniteHybrid", arch.num_local_experts,
                                    max_segments, seg, f, chosen)


def segment_features(arch: Arch, max_segments: int, dtype,
                     params: Mapping[str, Any], rows: jnp.ndarray
                     ) -> jnp.ndarray:
    """The device step: ``rows`` (B, 2, T) -> one line per segment
    (:func:`pool_segments`); per-token states never leave it."""
    f, chosen = token_states(arch, params, rows, dtype)
    return pool_segments(arch, max_segments, rows[:, 1], f, chosen)


def logits(arch: Arch, params: Mapping[str, Any], f: jnp.ndarray
           ) -> jnp.ndarray:
    """``f E^T / logits_scaling`` over the held rows of the tied embedding:
    for ``show_pred`` and the tests only."""
    return jnp.dot(f, params["embed"].astype(f.dtype).T,
                   precision=jax.lax.Precision.HIGHEST) / arch.logits_scaling
