"""DeepSeek-V2-Lite's forward pass, plainly: ``jax.numpy``, float32, matrix
products at precision "highest" (the caller sets
``jax.default_matmul_precision("highest")``), attention dense and head by
head, the chosen experts in a Python loop. No packing: it is given one
document (or one window of one) at a time, so there is no segment mask and
positions are 0 .. L - 1. It shares no arithmetic with
``models/deepseek_v2.py``; of the program it takes the architecture's
description (``Arch``) and the seeded weights (``layer_weights``,
``outer_weights``), unrounded.

With tokens ``t`` of one document (``config.json`` keys in brackets; ``H``
``num_attention_heads``, no bias anywhere)::

    x = E[id_t]
    for each layer i:  x += MLA(RMSNorm(x))
                       u  = RMSNorm(x)
                       x += Unit(u)                    i < first_k_dense_replace
                       x += sum_{e in top} p_e Expert_e(u) + Shared(u)   else
    f = RMSNorm(x)

A gated unit is ``(silu(u W_gate) * (u W_up)) W_down``: ``Unit``
``intermediate_size`` wide, ``Expert_e`` ``moe_intermediate_size``,
``Shared`` ``n_shared_experts x moe_intermediate_size``. Gate
(``scoring_func`` softmax, ``topk_method`` greedy): ``p = softmax(u W_g)``
over all ``n_routed_experts``, the ``num_experts_per_tok`` largest, divided
by their sum only under ``norm_topk_prob``, times ``routed_scaling_factor``.

MLA (``q_lora_rank`` null): ``q = u W_q`` as H heads of ``qk_nope_head_dim +
qk_rope_head_dim``, split ``q_nope | q_pe``; ``u W_kva`` split ``c``
(``kv_lora_rank``) ``| k_pe`` (``qk_rope_head_dim``, one a token, shared by
the heads); ``c <- RMSNorm(c)``; ``c W_kvb`` as H heads of ``qk_nope_head_dim
+ v_head_dim``, split ``k_nope | v``; rotary on ``q_pe`` and ``k_pe``;
``s_h = (q_nope_h . k_nope_h + q_pe_h . k_pe) * scale``, causal, softmax,
``o_h = softmax(s_h) v_h``; ``concat_h(o_h) W_o``.

YaRN (``rope_scaling``; ``d`` = ``qk_rope_head_dim``): ``f_i = theta^(-2i /
d)``; ``c(n) = d ln(original / (2 pi n)) / (2 ln theta)``, ``low =
max(floor(c(beta_fast)), 0)``, ``high = min(ceil(c(beta_slow)), d - 1)``,
``ramp_i = clip((i - low) / (high - low), 0, 1)``, ``inv_freq_i = f_i (1 -
ramp_i) + f_i / factor * ramp_i``. ``m(s, a) = 0.1 a ln s + 1``; cos and sin
times ``m(factor, mscale) / m(factor, mscale_all_dim)``; ``scale =
(qk_nope_head_dim + qk_rope_head_dim)^-0.5 * m(factor, mscale_all_dim)^2``.
The published layout pairs channels (2j, 2j + 1): a vector is de-interleaved
to ``[x_0, x_2, ... | x_1, x_3, ...]``, then ``x cos + rotate_half(x) sin``
with ``rotate_half(x) = [-x_{d/2:}, x_{:d/2}]`` and cos / sin of ``[p
inv_freq, p inv_freq]``.

Departures from the published code, each on purpose: (1) only the experts
``first_expert`` .. ``+ experts_held`` contribute (this chip's share of a
layer; with one chip a layer, all of them); (2) weights are seeded, not a
checkpoint, and a unit's ``gate_proj`` and ``up_proj`` are the halves of one
drawn matrix; (3) the model ends in its final RMSNorm (the untied head is on
the last pipeline stage) and features are the mean of ``f`` over a window's
tokens, the system's own definition; (4) ``routed_scaling_factor`` is
applied under ``norm_topk_prob`` too (the published code leaves it out
there; the published configuration does not renormalise).

``positions``, ``scale`` and ``renormalise`` can be overridden so that the
tests can show that the comparison notices each.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.deepseek_v2 import Arch


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight


def mscale(factor: float, a: float) -> float:
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def inv_freq(arch: Arch) -> np.ndarray:
    d, theta = arch.qk_rope_head_dim, arch.rope_theta

    def c(n):
        return d * math.log(arch.rope_original_max_position_embeddings
                            / (2 * math.pi * n)) / (2 * math.log(theta))

    low = max(math.floor(c(arch.rope_beta_fast)), 0)
    high = min(math.ceil(c(arch.rope_beta_slow)), d - 1)
    out = []
    for i in range(d // 2):
        f = theta ** (-2 * i / d)
        # the published ramp adds 0.001 to ``high`` where it equals ``low``
        ramp = min(max((i - low) / ((high + 0.001 if high == low else high)
                                    - low), 0.0), 1.0)
        out.append(f * (1 - ramp) + f / arch.rope_factor * ramp)
    return np.asarray(out)


def softmax_scale(arch: Arch) -> float:
    return (arch.qk_nope_head_dim + arch.qk_rope_head_dim) ** -0.5 \
        * mscale(arch.rope_factor, arch.rope_mscale_all_dim) ** 2


def rotary(arch: Arch, x, positions):
    """``x`` (T, d) at ``positions`` (T,)."""
    half = arch.qk_rope_head_dim // 2
    angles = jnp.asarray(positions, jnp.float32)[:, None] \
        * jnp.asarray(inv_freq(arch), jnp.float32)[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    m = mscale(arch.rope_factor, arch.rope_mscale) \
        / mscale(arch.rope_factor, arch.rope_mscale_all_dim)
    x = jnp.concatenate([x[:, 0::2], x[:, 1::2]], axis=-1)
    rotated = jnp.concatenate([-x[:, half:], x[:, :half]], axis=-1)
    return x * (jnp.cos(angles) * m) + rotated * (jnp.sin(angles) * m)


def attention(arch: Arch, w: Mapping[str, Any], u, positions, scale):
    t = u.shape[0]
    heads, nope, rope, v_dim, rank = (
        arch.num_attention_heads, arch.qk_nope_head_dim,
        arch.qk_rope_head_dim, arch.v_head_dim, arch.kv_lora_rank)
    q = (u @ w["q"]).reshape(t, heads, nope + rope)
    kv_a = u @ w["kv_a"]
    c = rms_norm(kv_a[:, :rank], w["kv_a_norm"], arch.rms_norm_eps)
    k_pe = rotary(arch, kv_a[:, rank:], positions)
    kv = (c @ w["kv_b"]).reshape(t, heads, nope + v_dim)
    causal = jnp.tril(jnp.ones((t, t), bool))
    out = []
    for h in range(heads):
        q_pe = rotary(arch, q[:, h, nope:], positions)
        scores = (q[:, h, :nope] @ kv[:, h, :nope].T + q_pe @ k_pe.T) * scale
        weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        out.append(weights @ kv[:, h, nope:])
    return jnp.concatenate(out, axis=-1) @ w["o"]


def gated(u, w_in, w_out):
    hidden = u @ w_in
    half = hidden.shape[-1] // 2
    return (jax.nn.silu(hidden[:, :half]) * hidden[:, half:]) @ w_out


def experts(arch: Arch, w: Mapping[str, Any], u, renormalise: bool):
    """``(held experts' part + shared experts, chosen (T, K))``."""
    p = jax.nn.softmax(u @ w["router"], axis=-1)
    gates, chosen = jax.lax.top_k(p, arch.num_experts_per_tok)
    if renormalise:
        gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-20)
    gates = gates * arch.routed_scaling_factor
    out = gated(u, w["shared_in"], w["shared_out"])
    for slot in range(arch.experts_held):
        gate = jnp.sum(jnp.where(chosen == arch.first_expert + slot,
                                 gates, 0.0), axis=-1)
        out = out + gate[:, None] * gated(u, w["experts_in"][slot],
                                          w["experts_out"][slot])
    return out, chosen


def token_states(arch: Arch, layer: Callable[[int], Mapping[str, Any]],
                 outer: Mapping[str, Any], ids, positions=None,
                 scale: Optional[float] = None,
                 renormalise: Optional[bool] = None) -> Tuple[Any, Any]:
    """One document's ``f`` (T, D) and every routed layer's choices (routed
    layers, T, K). ``layer(i)`` hands over layer ``i``'s float32 weights,
    one layer at a time (all of them need not fit at once)."""
    ids = jnp.asarray(ids)
    positions = np.arange(len(ids)) if positions is None else positions
    scale = softmax_scale(arch) if scale is None else scale
    renormalise = arch.norm_topk_prob if renormalise is None else renormalise
    x = outer["embed"][ids]
    chosen = []
    for i, kind in enumerate(arch.layer_kinds):
        w = layer(i)
        x = x + attention(arch, w["attn"],
                          rms_norm(x, w["norm1"], arch.rms_norm_eps),
                          positions, scale)
        u = rms_norm(x, w["norm2"], arch.rms_norm_eps)
        if kind == "dense":
            x = x + gated(u, w["mlp_in"], w["mlp_out"])
        else:
            out, picks = experts(arch, w, u, renormalise)
            x = x + out
            chosen.append(picks)
    return rms_norm(x, outer["final_norm"], arch.rms_norm_eps), \
        jnp.stack(chosen)


def windows_of(n: int, window: int, step: int):
    """``[(start, end)]``: windows of ``window`` tokens every ``step``, the
    last one as short as the document leaves it."""
    count = 1 if n <= window else -(-(n - window) // step) + 1
    return [(i * step, min(i * step + window, n)) for i in range(count)]


def features(arch: Arch, layer, outer, ids, window: int, step: int
             ) -> Tuple[np.ndarray, np.ndarray]:
    """A document's features ``(windows, D)`` and expert counts ``(windows,
    routed layers, n_routed_experts)``: every window is run as a document
    of its own, from position 0."""
    feats, counts = [], []
    for start, end in windows_of(len(ids), window, step):
        f, chosen = token_states(arch, layer, outer, ids[start:end])
        feats.append(np.asarray(f.mean(axis=0)))
        counts.append(np.asarray(jax.nn.one_hot(
            chosen, arch.n_routed_experts).sum(axis=(1, 2))))
    return np.stack(feats), np.stack(counts).astype(np.int32)
