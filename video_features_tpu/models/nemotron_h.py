"""NVIDIA-Nemotron-3-Super-120B-A12B (``model_type`` nemotron_h) as a feature
model over packed rows of tokens.

A residual stream; every layer is ONE block behind an RMSNorm, ``x <- x +
block(RMSNorm(x))``, of the kind ``hybrid_override_pattern`` gives it:

- ``M``, Mamba-2: ``in_proj`` to ``[z | x B C | dt]``, a causal convolution
  with a bias over ``x B C``, the state-space scan (``ops/ssd.py``) with
  ``B``/``C`` in ``n_groups`` groups, ``D`` skip, then an RMSNorm of ``y *
  silu(z)`` taken per group of ``d_inner / n_groups`` channels, ``out_proj``.
- ``*``, grouped-query attention with no position embedding (the published
  code applies none; ``rope_theta`` is read by no layer).
- ``E``, LatentMoE: a sigmoid router over ``n_routed_experts`` chooses
  ``num_experts_per_tok`` by ``sigmoid + e_score_correction_bias`` and
  gates with the chosen sigmoids, renormalised and scaled by
  ``routed_scaling_factor``; the chosen experts are non-gated ``relu2``
  units that run in a ``moe_latent_size``-wide latent between two
  projections (device scope ``moe/latent``); beside them a shared ``relu2``
  unit on the full width.

The equations are in ``reference/nemotron_h.py``, the plain copy the tests
hold this file to. What this chip holds of a layer is part of the
architecture (:class:`Arch`), as in the other token families: experts
``first_expert`` .. ``first_expert + experts_held - 1`` of
``n_routed_experts`` and rows ``0`` .. ``vocab_held - 1`` of the embedding;
the router stays full width. The cut model ends in its final RMSNorm (the
head and the multi-token-prediction module follow the last layer).

Weights are made on the device, layer by layer, from the seed
(:func:`layer_weights`, float32, which the reference calls too) and rounded
once to the serving type inside the same program; the selection bias is
drawn too (:data:`SELECTION_BIAS_STD`): a bias of zeros would leave the
selection rule untested.
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
from .token_rows import INIT_STD, part_key, pool_segments, rms_norm

FAMILY = "NemotronH"
#: ``e_score_correction_bias`` is normal(0, SELECTION_BIAS_STD) a layer: a
#: checkpoint's is learned, a seeded one of zeros would never move a choice
SELECTION_BIAS_STD = 0.05
#: a block's kind by its character in ``hybrid_override_pattern``
BLOCKS = {"M": "mamba", "*": "attn", "E": "moe"}


@dataclass(frozen=True)
class Arch:
    """The published ``config.json`` keys the forward pass reads, and this
    chip's share."""
    hidden_size: int
    hybrid_override_pattern: str
    vocab_size: int
    layer_norm_epsilon: float
    mamba_num_heads: int
    mamba_head_dim: int
    ssm_state_size: int
    n_groups: int
    conv_kernel: int
    chunk_size: int
    time_step_min: float
    time_step_max: float
    time_step_floor: float
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    n_routed_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    moe_latent_size: int
    moe_shared_expert_intermediate_size: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    # -- this chip's share of a layer
    first_expert: int
    experts_held: int
    vocab_held: int

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple(BLOCKS[c] for c in self.hybrid_override_pattern)

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

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
    nemotron_h.yml``'s ``architecture``), cut in depth by
    ``num_hidden_layers`` (``hybrid_override_pattern`` its first
    characters); ``layer_shards`` chips share each layer: each holds ``1 /
    layer_shards`` of the routed experts and of the vocabulary."""
    depth = int(published["num_hidden_layers"])
    pattern = str(published["hybrid_override_pattern"])[:depth]
    for what, refused in (
            ("a bias in a projection", any(
                bool(published.get(k, False)) for k in (
                    "mamba_proj_bias", "attention_bias", "mlp_bias",
                    "use_bias"))),
            ("use_conv_bias false", not published.get("use_conv_bias", True)),
            ("an activation other than relu2",
             published.get("mlp_hidden_act", "relu2") != "relu2"),
            ("grouped routing (n_group, topk_group)",
             int(published.get("n_group", 1)) != 1
             or int(published.get("topk_group", 1)) != 1),
            ("other than one shared expert",
             int(published.get("n_shared_experts", 1)) != 1),
            ("no moe_latent_size", published.get("moe_latent_size") is None),
            ("a block other than M, * and E", not set(pattern) <= set(BLOCKS)),
            ("fewer pattern characters than num_hidden_layers",
             len(pattern) < depth),
            ("mamba_num_heads x mamba_head_dim other than expand x "
             "hidden_size",
             int(published["mamba_num_heads"])
             * int(published["mamba_head_dim"])
             != int(published.get("expand", 2))
             * int(published["hidden_size"])),
            # the step returns every routed layer's counts beside a feature
            ("a cut that leaves no E layer", "E" not in pattern)):
        if refused:
            raise NotImplementedError(f"nemotron_h: {what}")
    experts = int(published["n_routed_experts"])
    vocab = int(published["vocab_size"])
    shards, rank = int(layer_shards), int(layer_shard_rank)
    if experts % shards or vocab % shards or not 0 <= rank < shards:
        raise ValueError(f"layer_shards={shards}, layer_shard_rank={rank}: "
                         f"cannot divide {experts} experts and {vocab} "
                         "vocabulary rows")
    # numbers arrive from YAML or a command line: ``1e-05`` as a string
    cast = {"int": int, "float": float, "bool": bool, "str": str}
    share = {"hybrid_override_pattern", "first_expert", "experts_held",
             "vocab_held"}
    return Arch(hybrid_override_pattern=pattern,
                first_expert=rank * (experts // shards),
                experts_held=experts // shards, vocab_held=vocab // shards,
                **{name: cast[field.type](published[name])
                   for name, field in Arch.__dataclass_fields__.items()
                   if name not in share})


# -- weights -----------------------------------------------------------------

def _normal(key, shape):
    return INIT_STD * jax.random.normal(key, shape, jnp.float32)


def _mamba_weights(arch: Arch, key) -> Dict[str, jnp.ndarray]:
    """By the published initialisation: ``A_log = log(1 .. H)``, ``D``
    ones, ``dt`` log-uniform in [``time_step_min``, ``time_step_max``],
    held at ``time_step_floor``, through the inverse softplus; the
    convolution by torch's Conv1d default for a fan-in of
    ``conv_kernel``."""
    k = jax.random.split(key, 5)
    h, d_in = arch.mamba_num_heads, arch.d_inner
    proj = d_in + arch.conv_dim + h
    dt = jnp.exp(jax.random.uniform(
        k[2], (h,), jnp.float32, math.log(arch.time_step_min),
        math.log(arch.time_step_max)))
    dt = jnp.maximum(dt, arch.time_step_floor)
    bound = 1.0 / math.sqrt(arch.conv_kernel)
    return {
        "in_proj": _normal(k[0], (arch.hidden_size, proj)),
        "conv_w": jax.random.uniform(k[1], (arch.conv_kernel, arch.conv_dim),
                                     jnp.float32, -bound, bound),
        "conv_b": jax.random.uniform(k[3], (arch.conv_dim,), jnp.float32,
                                     -bound, bound),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)),
        "D": jnp.ones((h,), jnp.float32),
        "norm": jnp.ones((d_in,), jnp.float32),
        "out_proj": _normal(k[4], (d_in, arch.hidden_size)),
    }


def _attention_weights(arch: Arch, key) -> Dict[str, jnp.ndarray]:
    k = jax.random.split(key, 4)
    d, hd = arch.hidden_size, arch.head_dim
    q, kv = arch.num_attention_heads * hd, arch.num_key_value_heads * hd
    shapes = {"q": (d, q), "k": (d, kv), "v": (d, kv), "o": (q, d)}
    return {name: _normal(k[i], shape)
            for i, (name, shape) in enumerate(shapes.items())}


def _moe_weights(arch: Arch, key) -> Dict[str, jnp.ndarray]:
    """Expert ``e`` is drawn under its own key, so a chip that holds
    another share draws the same expert."""
    k = jax.random.split(key, 8)
    d, lat = arch.hidden_size, arch.moe_latent_size
    i, s = arch.moe_intermediate_size, arch.moe_shared_expert_intermediate_size

    def expert(e):
        e_in, e_out = jax.random.split(jax.random.fold_in(k[7], e))
        return _normal(e_in, (lat, i)), _normal(e_out, (i, lat))

    experts_in, experts_out = jax.vmap(expert)(
        arch.first_expert + jnp.arange(arch.experts_held))
    return {"router": _normal(k[0], (d, arch.n_routed_experts)),
            "selection_bias": SELECTION_BIAS_STD * jax.random.normal(
                k[1], (arch.n_routed_experts,), jnp.float32),
            "latent_down": _normal(k[2], (d, lat)),
            "latent_up": _normal(k[3], (lat, d)),
            "experts_in": experts_in, "experts_out": experts_out,
            "shared_in": _normal(k[4], (d, s)),
            "shared_out": _normal(k[5], (s, d))}


_DRAW = {"mamba": _mamba_weights, "attn": _attention_weights,
         "moe": _moe_weights}


def _draw(arch: Arch, kind: str, key) -> Dict[str, Any]:
    if kind == "outer":
        return token_rows.draw_outer(arch, key)
    return {"pre_norm": jnp.ones((arch.hidden_size,), jnp.float32),
            **_DRAW[kind](arch, key)}


_draw_float32 = jax.jit(_draw, static_argnums=(0, 1))


def layer_weights(arch: Arch, seed: int, index: int) -> Dict[str, Any]:
    """Layer ``index``'s float32 weights from the seed, this chip's experts
    only. With :func:`outer_weights`, where the program and the plain
    reference both take their weights from."""
    return _draw_float32(arch, arch.layer_kinds[index], part_key(seed, index))


def outer_weights(arch: Arch, seed: int) -> Dict[str, jnp.ndarray]:
    """The held rows of the embedding and the final norm, float32; row ``r``
    is the same whatever slice holds it."""
    return _draw_float32(arch, "outer",
                         part_key(seed, len(arch.layer_kinds)))


def init_params(arch: Arch, seed: int, dtype, sharding=None) -> Dict[str, Any]:
    """The whole tree in ``dtype`` on the device, drawn layer by layer
    (``token_rows.init_params``)."""
    return token_rows.init_params(functools.partial(_draw, arch),
                                  arch.layer_kinds, seed, dtype, sharding)


# -- the forward pass --------------------------------------------------------

def gated_group_norm(y: jnp.ndarray, z: jnp.ndarray, weight: jnp.ndarray,
                     groups: int, eps: float, axis: int = -1) -> jnp.ndarray:
    """``y * silu(z)`` RMS-normed per group of ``C / groups`` consecutive
    channels (the published ``MambaRMSNormGated``), float32 inside and out:
    ``y``, ``z`` with their ``C`` channels on ``axis``, returned split into
    (``groups``, ``C / groups``) there."""
    axis %= y.ndim
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    parts = gated.reshape(*gated.shape[:axis], groups, -1,
                          *gated.shape[axis + 1:])
    return rms_norm(parts, weight.reshape(groups, -1), eps, axis=axis + 1)


def mamba_mixer(arch: Arch, w: Mapping[str, jnp.ndarray], u: jnp.ndarray,
                seg: jnp.ndarray, state_dtype=jnp.float32) -> jnp.ndarray:
    """``u`` (B, T, D) -> (B, T, D) float32, in the scan's layout between
    the projections as granite's (``models/granite_hybrid.py
    mamba_mixer``)."""
    bsz, t, _ = u.shape
    h, p, n = arch.mamba_num_heads, arch.mamba_head_dim, arch.ssm_state_size
    g, d_in, q = arch.n_groups, arch.d_inner, arch.chunk_size
    z, xbc, dt, seg = ssd.mamba_in(w, u, seg, d_in, arch.conv_dim, q)
    nc = seg.shape[1]
    xs = xbc[:, :, :d_in].reshape(bsz, nc, h, p, q)
    b = xbc[:, :, d_in:d_in + g * n].reshape(bsz, nc, g, n, q)
    c = xbc[:, :, d_in + g * n:].reshape(bsz, nc, g, n, q)
    with scope("ssd"):
        y = ssd.ssd_chunks(xs, dt, -jnp.exp(w["A_log"].astype(jnp.float32)),
                           b, c, seg, state_dtype)
    y = y + xs.astype(jnp.float32) * w["D"].astype(jnp.float32)[:, None, None]
    y = gated_group_norm(y.reshape(bsz, nc, d_in, q), z, w["norm"], g,
                         arch.layer_norm_epsilon, axis=2).astype(u.dtype)
    # contracted a group at a time, in the shape the norm leaves: merged
    # back into one axis first, the norm's scale is written out at full
    # width before the product reads it
    out = jnp.einsum("bcgkq,gkd->bcqd", y,
                     w["out_proj"].reshape(g, d_in // g, -1),
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
    with scope("core"):
        out = blockwise_attention(q, k, v, causal=True, segment_ids=seg,
                                  scale=hd ** -0.5)
    return jnp.dot(out.reshape(bsz, t, heads * hd), w["o"],
                   preferred_element_type=jnp.float32)


def latent_moe(arch: Arch, w: Mapping[str, jnp.ndarray], u: jnp.ndarray,
               valid: jnp.ndarray, router_dtype=jnp.float32
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``u`` (T, D) -> the held experts' part plus the shared unit's (T, D)
    float32, and the router's choices (T, K). The router reads ``u``; the
    chosen experts read its latent projection and their gated sum is
    projected back."""
    gates, picks = moe.route(
        u, w["router"], arch.num_experts_per_tok, router_dtype,
        rule="sigmoid", selection_bias=w["selection_bias"],
        renormalise=arch.norm_topk_prob, renormalise_eps=1e-20,
        scaling=arch.routed_scaling_factor)
    with scope("latent"):
        latent = jnp.dot(u, w["latent_down"],
                         preferred_element_type=jnp.float32).astype(u.dtype)
    routed = moe.held_experts(latent, gates, picks, w["experts_in"],
                              w["experts_out"], arch.first_expert, valid,
                              arch.n_routed_experts, activation="relu2")
    with scope("latent"):
        routed = jnp.dot(routed.astype(u.dtype), w["latent_up"],
                         preferred_element_type=jnp.float32)
    return routed, picks


def token_states(arch: Arch, params: Mapping[str, Any], rows: jnp.ndarray,
                 dtype, state_dtype=jnp.float32, router_dtype=jnp.float32
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``rows`` (B, 2, T) int32 -> the final hidden states ``f`` (B, T, D)
    float32 (after the last RMSNorm) and every E layer's choices (E layers,
    B, T, K)."""
    ids, seg = rows[:, 0], rows[:, 1]
    bsz, t = ids.shape
    valid = (seg > 0).reshape(-1)
    eps = arch.layer_norm_epsilon
    with scope(FAMILY, "embed"):
        x = jnp.take(params["embed"], ids, axis=0).astype(dtype)
    chosen = []
    for kind, w in zip(arch.layer_kinds, params["layers"]):
        with scope(FAMILY, kind):
            u = rms_norm(x, w["pre_norm"], eps)
            if kind == "mamba":
                out = mamba_mixer(arch, w, u, seg, state_dtype)
            elif kind == "attn":
                out = attention_mixer(arch, w, u, seg)
            else:
                u = u.reshape(bsz * t, -1)
                out, picks = latent_moe(arch, w, u, valid, router_dtype)
                chosen.append(picks.reshape(bsz, t, -1))
        if kind == "moe":
            with scope(FAMILY, "shared_mlp"):
                out = out + moe.gated_unit(u, w["shared_in"], w["shared_out"],
                                           activation="relu2")
        x = (x.astype(jnp.float32) + out.reshape(bsz, t, -1)).astype(dtype)
    with scope(FAMILY, "pool"):
        f = rms_norm(x.astype(jnp.float32), params["final_norm"], eps)
    return f, jnp.stack(chosen)


def segment_features(arch: Arch, max_segments: int, dtype,
                     params: Mapping[str, Any], rows: jnp.ndarray
                     ) -> jnp.ndarray:
    """The device step: ``rows`` (B, 2, T) -> one line per segment
    (``token_rows.pool_segments``); per-token states never leave it."""
    f, chosen = token_states(arch, params, rows, dtype)
    return pool_segments(FAMILY, arch.n_routed_experts, max_segments,
                         rows[:, 1], f, chosen)
