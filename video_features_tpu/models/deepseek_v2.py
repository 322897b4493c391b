"""DeepSeek-V2-Lite (``model_type`` deepseek_v2) as a feature model over
packed rows of tokens.

A residual stream; every layer is multi-head latent attention (MLA) with
YaRN-scaled rotary positions and then a feed-forward block, each behind an
RMSNorm. The first ``first_k_dense_replace`` layers' feed-forward is one
gated unit; the others route every token to ``num_experts_per_tok`` of
``n_routed_experts`` experts (softmax over all, the largest probabilities,
not renormalised) beside ``n_shared_experts`` shared ones. The equations are
in ``reference/deepseek_v2.py``, the plain copy the tests hold this file to.

MLA runs in its expanded form: keys and values are up-projected per head
from the 512-wide latent and attention is ordinary multi-head attention with
192-wide query/key heads (128 without position + 64 rotary, the rotary key
one vector a token shared by the heads) and 128-wide value heads. This
system prefills and never decodes, so there is no cache for the latent to
shrink, and the absorbed form (attention over the 576-wide latent itself)
only adds operations: 2 x 576 + 2 x 512 a (query, key, head) against 2 x 192
+ 2 x 128. There is one form and no key that chooses.

Positions restart at every segment of a row: they are derived on the device
from the segment ids (``token_rows.segment_positions``), so the row stays
``(2, T) int32``. What this chip holds of a layer is part of the
architecture (:class:`Arch`), as in ``models/granite_hybrid.py``: experts
``first_expert`` .. ``first_expert + experts_held - 1`` of
``n_routed_experts`` and rows ``0`` .. ``vocab_held - 1`` of the vocabulary;
the router stays full width. The output head is untied and lives on the last
pipeline stage: this model ends in the final RMSNorm.

Weights are made on the device, layer by layer, from the seed
(:func:`layer_weights`, float32, which the reference calls too) and rounded
once to the serving type inside the same program.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import moe
from ..parallel.sequence import blockwise_attention
from . import token_rows
from .common import scope
from .token_rows import (INIT_STD, part_key, pool_segments, rms_norm,
                         segment_positions)

FAMILY = "DeepSeekV2"


@dataclass(frozen=True)
class Arch:
    """The published ``config.json`` keys the forward pass reads
    (``rope_scaling``'s under ``rope_<key>``), and this chip's share."""
    hidden_size: int
    num_hidden_layers: int
    vocab_size: int
    rms_norm_eps: float
    num_attention_heads: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kv_lora_rank: int
    rope_theta: float
    rope_factor: float
    rope_original_max_position_embeddings: int
    rope_beta_fast: float
    rope_beta_slow: float
    rope_mscale: float
    rope_mscale_all_dim: float
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    first_k_dense_replace: int
    moe_layer_freq: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    # -- this chip's share of a layer
    first_expert: int
    experts_held: int
    vocab_held: int

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """``dense`` or ``moe`` for every layer, by the published rule."""
        return tuple(
            "moe" if i >= self.first_k_dense_replace
            and i % self.moe_layer_freq == 0 else "dense"
            for i in range(self.num_hidden_layers))

    @property
    def feature_dim(self) -> int:
        return self.hidden_size

    @property
    def counter_shape(self) -> Tuple[int, int]:
        """(routed layers, the router's width) of a line's counts."""
        return self.layer_kinds.count("moe"), self.n_routed_experts

    @property
    def counter_dim(self) -> int:
        return math.prod(self.counter_shape)


def arch_from_config(published: Mapping[str, Any], layer_shards: int = 1,
                     layer_shard_rank: int = 0) -> Arch:
    """``published`` is the model's ``config.json`` (``configs/
    deepseek_v2.yml``'s ``architecture``), cut in depth by
    ``num_hidden_layers``; ``layer_shards`` chips share each layer: each
    holds ``1 / layer_shards`` of the routed experts and of the vocabulary."""
    rope = dict(published.get("rope_scaling") or {})
    for what, refused in (
            ("q_lora_rank", published.get("q_lora_rank") is not None),
            ("rope_scaling other than yarn", rope.get("type") != "yarn"),
            ("scoring_func other than softmax",
             published.get("scoring_func", "softmax") != "softmax"),
            ("topk_method other than greedy",
             published.get("topk_method", "greedy") != "greedy"),
            ("attention_bias", bool(published.get("attention_bias"))),
            ("hidden_act other than silu",
             published.get("hidden_act", "silu") != "silu"),
            ("grouped keys and values",
             published.get("num_key_value_heads",
                           published["num_attention_heads"])
             != published["num_attention_heads"]),
            # the step returns every routed layer's counts beside a feature
            ("a cut that leaves no routed layer",
             int(published["num_hidden_layers"])
             <= int(published["first_k_dense_replace"]))):
        if refused:
            raise NotImplementedError(f"deepseek_v2: {what}")
    experts, vocab = (int(published["n_routed_experts"]),
                      int(published["vocab_size"]))
    shards, rank = int(layer_shards), int(layer_shard_rank)
    if experts % shards or vocab % shards or not 0 <= rank < shards:
        raise ValueError(f"layer_shards={shards}, layer_shard_rank={rank}: "
                         f"cannot divide {experts} experts and {vocab} "
                         "vocabulary rows")
    flat = {**published, **{f"rope_{k}": v for k, v in rope.items()}}
    # numbers arrive from YAML or a command line: ``1e-06`` as a string
    cast = {"int": int, "float": float, "bool": bool}
    share = {"first_expert", "experts_held", "vocab_held"}
    return Arch(first_expert=rank * (experts // shards),
                experts_held=experts // shards, vocab_held=vocab // shards,
                **{name: cast[field.type](flat[name])
                   for name, field in Arch.__dataclass_fields__.items()
                   if name not in share})


# -- YaRN ------------------------------------------------------------------------

def yarn_bounds(arch: Arch) -> Tuple[int, int]:
    """``(low, high)``: the rotary channel pairs between which the
    frequencies go from kept to divided by ``factor``: the pairs that turn
    ``beta_fast`` and ``beta_slow`` times over the original length."""
    d = arch.qk_rope_head_dim

    def pair_of(rotations: float) -> float:
        return d * math.log(arch.rope_original_max_position_embeddings
                            / (rotations * 2 * math.pi)) \
            / (2 * math.log(arch.rope_theta))

    return (max(math.floor(pair_of(arch.rope_beta_fast)), 0),
            min(math.ceil(pair_of(arch.rope_beta_slow)), d - 1))


def yarn_inv_freq(arch: Arch) -> np.ndarray:
    """(qk_rope_head_dim / 2,) float64: ``theta^(-2i/d)``, kept below
    ``low``, divided by ``factor`` above ``high``, a linear blend between."""
    d = arch.qk_rope_head_dim
    i = np.arange(d // 2, dtype=np.float64)
    kept = arch.rope_theta ** (-2.0 * i / d)
    low, high = yarn_bounds(arch)
    # the published ramp's guard against low == high
    ramp = np.clip((i - low) / ((high if high != low else high + 0.001)
                                - low), 0.0, 1.0)
    return kept * (1.0 - ramp) + kept / arch.rope_factor * ramp


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary_scale(arch: Arch) -> float:
    """What cos and sin are multiplied by (1 where ``mscale`` ==
    ``mscale_all_dim``)."""
    return yarn_mscale(arch.rope_factor, arch.rope_mscale) \
        / yarn_mscale(arch.rope_factor, arch.rope_mscale_all_dim)


def softmax_scale(arch: Arch) -> float:
    """``qk_head_dim^-0.5 * m(factor, mscale_all_dim)^2``."""
    return arch.qk_head_dim ** -0.5 \
        * yarn_mscale(arch.rope_factor, arch.rope_mscale_all_dim) ** 2


# -- weights -------------------------------------------------------------------

def _normal(key, shape):
    return INIT_STD * jax.random.normal(key, shape, jnp.float32)


def _draw_layer(arch: Arch, kind: str, key) -> Dict[str, Any]:
    """A gated unit's ``gate_proj`` and ``up_proj`` are one matrix ``[gate |
    up]``, as ``ops/moe.py gated_unit`` takes them."""
    k_attn, k_router, k_in, k_out, k_experts = jax.random.split(key, 5)
    d, heads = arch.hidden_size, arch.num_attention_heads
    k = jax.random.split(k_attn, 4)
    layer = {
        "norm1": jnp.ones((d,), jnp.float32),
        "attn": {
            "q": _normal(k[0], (d, heads * arch.qk_head_dim)),
            "kv_a": _normal(k[1], (d, arch.kv_lora_rank
                                   + arch.qk_rope_head_dim)),
            "kv_a_norm": jnp.ones((arch.kv_lora_rank,), jnp.float32),
            "kv_b": _normal(k[2], (arch.kv_lora_rank, heads * (
                arch.qk_nope_head_dim + arch.v_head_dim))),
            "o": _normal(k[3], (heads * arch.v_head_dim, d)),
        },
        "norm2": jnp.ones((d,), jnp.float32),
    }
    if kind == "dense":
        i = arch.intermediate_size
        return {**layer, "mlp_in": _normal(k_in, (d, 2 * i)),
                "mlp_out": _normal(k_out, (i, d))}
    i = arch.moe_intermediate_size
    s = arch.n_shared_experts * i

    def expert(e):
        e_in, e_out = jax.random.split(jax.random.fold_in(k_experts, e))
        return _normal(e_in, (d, 2 * i)), _normal(e_out, (i, d))

    experts_in, experts_out = jax.vmap(expert)(
        arch.first_expert + jnp.arange(arch.experts_held))
    return {**layer,
            "router": _normal(k_router, (d, arch.n_routed_experts)),
            "experts_in": experts_in, "experts_out": experts_out,
            "shared_in": _normal(k_in, (d, 2 * s)),
            "shared_out": _normal(k_out, (s, d))}


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
    """The held rows of the embedding and the final norm, float32; row ``r``
    is the same whatever slice holds it."""
    return _draw_float32(arch, "outer",
                         part_key(seed, arch.num_hidden_layers))


def init_params(arch: Arch, seed: int, dtype, sharding=None) -> Dict[str, Any]:
    """The whole tree in ``dtype`` on the device, drawn layer by layer
    (``token_rows.init_params``)."""
    return token_rows.init_params(functools.partial(_draw, arch),
                                  arch.layer_kinds, seed, dtype, sharding)


# -- the forward pass ------------------------------------------------------------

def rotary_tables(arch: Arch, seg: jnp.ndarray
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos and sin (B, T, qk_rope_head_dim / 2) float32 of every token's
    position within its segment. Angles stay float32: position 16,383 times
    a frequency near 1 has no phase left in bfloat16."""
    angles = segment_positions(seg).astype(jnp.float32)[..., None] \
        * jnp.asarray(yarn_inv_freq(arch), jnp.float32)
    m = rotary_scale(arch)
    return jnp.cos(angles) * m, jnp.sin(angles) * m


def rotate(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """The published layout pairs channels (2j, 2j + 1): ``x`` (..., d) is
    de-interleaved to ``[evens | odds]`` and comes back as ``x cos +
    rotate_half(x) sin`` in that order, float32 inside. ``cos`` / ``sin``
    broadcast against (..., d / 2)."""
    x32 = x.astype(jnp.float32)
    even, odd = x32[..., 0::2], x32[..., 1::2]
    return jnp.concatenate([even * cos - odd * sin, odd * cos + even * sin],
                           axis=-1).astype(x.dtype)


def latent_attention(arch: Arch, w: Mapping[str, jnp.ndarray], u: jnp.ndarray,
                     seg: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray
                     ) -> jnp.ndarray:
    """MLA, expanded, causal and within a segment: ``u`` (B, T, D) ->
    (B, T, D) float32."""
    bsz, t, _ = u.shape
    heads, nope, v_dim = (arch.num_attention_heads, arch.qk_nope_head_dim,
                          arch.v_head_dim)

    def project(x, name):
        return jnp.dot(x, w[name], preferred_element_type=jnp.float32
                       ).astype(u.dtype)

    q = project(u, "q").reshape(bsz, t, heads, arch.qk_head_dim)
    kv_a = project(u, "kv_a")
    latent = rms_norm(kv_a[..., :arch.kv_lora_rank], w["kv_a_norm"],
                      arch.rms_norm_eps)
    kv = project(latent, "kv_b").reshape(bsz, t, heads, nope + v_dim)
    with scope("rope"):
        q_pe = rotate(q[..., nope:], cos[:, :, None], sin[:, :, None])
        k_pe = rotate(kv_a[..., arch.kv_lora_rank:], cos, sin)
        # the one rotary key a token is every head's: broadcast beside the
        # per-head part, one 192-wide contraction in the fold
        q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_pe[:, :, None], (bsz, t, heads, arch.qk_rope_head_dim))],
            axis=-1)
    with scope("core"):
        out = blockwise_attention(q, k, kv[..., nope:], causal=True,
                                  segment_ids=seg, scale=softmax_scale(arch))
    return jnp.dot(out.reshape(bsz, t, heads * v_dim), w["o"],
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
    with scope(FAMILY, "embed"):
        x = jnp.take(params["embed"], ids, axis=0).astype(dtype)
    with scope(FAMILY, "attn", "rope"):
        cos, sin = rotary_tables(arch, seg)

    def add(x, out):
        return (x.astype(jnp.float32) + out.reshape(bsz, t, -1)).astype(dtype)

    chosen = []
    for kind, w in zip(arch.layer_kinds, params["layers"]):
        with scope(FAMILY, "attn"):
            u = rms_norm(x, w["norm1"], arch.rms_norm_eps)
            x = add(x, latent_attention(arch, w["attn"], u, seg, cos, sin))
        if kind == "dense":
            with scope(FAMILY, "dense_mlp"):
                u = rms_norm(x, w["norm2"], arch.rms_norm_eps)
                x = add(x, moe.gated_unit(u, w["mlp_in"], w["mlp_out"]))
            continue
        with scope(FAMILY, "moe"):
            u = rms_norm(x, w["norm2"], arch.rms_norm_eps
                         ).reshape(bsz * t, -1)
            gates, picks = moe.route(
                u, w["router"], arch.num_experts_per_tok, router_dtype,
                rule="softmax_topk", renormalise=arch.norm_topk_prob,
                scaling=arch.routed_scaling_factor)
            routed = moe.held_experts(u, gates, picks, w["experts_in"],
                                      w["experts_out"], arch.first_expert,
                                      valid, arch.n_routed_experts)
        with scope(FAMILY, "shared_mlp"):
            x = add(x, routed + moe.gated_unit(u, w["shared_in"],
                                               w["shared_out"]))
        chosen.append(picks.reshape(bsz, t, -1))
    with scope(FAMILY, "pool"):
        f = rms_norm(x.astype(jnp.float32), params["final_norm"],
                     arch.rms_norm_eps)
    return f, jnp.stack(chosen)


def segment_features(arch: Arch, max_segments: int, dtype,
                     params: Mapping[str, Any], rows: jnp.ndarray
                     ) -> jnp.ndarray:
    """The device step: ``rows`` (B, 2, T) -> one line per segment
    (``token_rows.pool_segments``); per-token states never leave it."""
    f, chosen = token_states(arch, params, rows, dtype)
    return pool_segments(FAMILY, arch.n_routed_experts, max_segments,
                         rows[:, 1], f, chosen)
