"""LFM2-8B-A1B's forward pass (``model_type`` lfm2_moe), plainly:
``jax.numpy``, float32, matrix products at precision "highest" (the caller
sets ``jax.default_matmul_precision("highest")``), attention dense and head
by head, the convolution's taps one by one, the chosen experts in a Python
loop. No packing: it is given one document (or one window of one) at a
time, so there is no segment mask and positions are 0 .. L - 1. It shares no
arithmetic with ``models/lfm2_moe.py``; of the program it takes the
architecture's description (``Arch``) and the seeded weights
(``layer_weights``, ``outer_weights``), unrounded.

With tokens ``t`` of one document (``config.json`` keys in brackets; ``D``
``hidden_size``, ``H`` ``num_attention_heads`` of ``d = D / H``, ``G``
``num_key_value_heads``, no bias anywhere: ``conv_bias`` false)::

    RMSNorm(x) = x rsqrt(mean(x^2) + norm_eps) w
    x = E[id_t]
    for each layer i:  h  = x + Op_i(RMSNorm(x))       Op by layer_types[i]
                       x' = h + Unit(RMSNorm(h))        i < num_dense_layers
                       x' = h + Routed(RMSNorm(h))      else
    f = RMSNorm(x)

Conv (``conv``, ``L`` = ``conv_L_cache`` taps): ``[B | C | x] = u W_in``
(``W_in`` D x 3D, split in that order); ``y_t = C_t * sum_{j=0..L-1} k_j *
(B * x)_{t-L+1+j}``, a tap before the document's first token reading zero;
``y W_out``.

Attention (``full_attention``): ``q = RMSNorm_q(u W_q)``, ``k = RMSNorm_k(u
W_k)`` per d-wide head, ``v = u W_v``; rotary with ``inv_freq_i =
rope_theta^(-2i / d)`` over the whole head in the ``rotate_half`` layout,
``x cos + [-x_{d/2:}, x_{:d/2}] sin`` with cos / sin of ``[p inv_freq, p
inv_freq]``; query head ``h`` reads key and value head ``h // (H / G)``;
``softmax(q k^T d^-0.5)`` causal; ``concat_h(o_h) W_o``.

A gated unit is ``(silu(u W_1) * (u W_3)) W_2``: ``Unit`` ``intermediate_size``
wide, ``Expert_e`` ``moe_intermediate_size``. Routed (``num_experts``,
``num_experts_per_tok`` = K): ``s = sigmoid(u W_r)`` over every expert;
``chosen = top_K(s + expert_bias)``; ``g = s[chosen] / (sum s[chosen] +
1e-6)`` (``norm_topk_prob``) ``* routed_scaling_factor``; ``sum_{e in
chosen} g_e Expert_e(u)``.

Departures from the published code, each on purpose: (1) only the experts
``first_expert`` .. ``+ experts_held`` contribute (this chip's share of a
layer; with one chip a layer, all of them); (2) weights are seeded, not a
checkpoint: a unit's ``w1`` and ``w3`` are the halves of one drawn matrix,
the norms are ones and ``expert_bias``, a buffer a checkpoint carries, is
drawn normal(0, 0.05); (3) the model ends in its final RMSNorm
(``embedding_norm``; the tied head is on the last pipeline stage) and
features are the mean of ``f`` over a window's tokens, the system's own
definition.

``selection_bias``, ``bias_in_gates``, ``scoring`` and ``rotary`` can be
changed so that the tests can show that the comparison notices each.
"""
from __future__ import annotations

from typing import Any, Callable, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.lfm2_moe import Arch


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight


def short_conv(w: Mapping[str, Any], u):
    """``u`` (T, D) of one document."""
    b, c, x = jnp.split(u @ w["in_proj"], 3, axis=-1)
    taps = w["conv_w"]                                          # (L, D)
    length = taps.shape[0]
    padded = jnp.concatenate([jnp.zeros((length - 1, u.shape[1])), b * x])
    conv = sum(taps[j] * padded[j:j + u.shape[0]] for j in range(length))
    return (c * conv) @ w["out_proj"]


def rotary(x, positions, theta: float, layout: str):
    """``x`` (T, heads, d) at ``positions`` (T,): the published
    ``rotate_half`` layout, or ``interleaved`` (pairs 2j, 2j + 1) for the
    tests."""
    d = x.shape[-1]
    angles = jnp.asarray(positions, jnp.float32)[:, None] * jnp.asarray(
        theta ** (-np.arange(0, d, 2) / d), jnp.float32)[None, :]
    angles = angles[:, None, :]                                 # (T, 1, d/2)
    if layout == "interleaved":
        even, odd = x[..., 0::2], x[..., 1::2]
        cos, sin = jnp.cos(angles), jnp.sin(angles)
        return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                         axis=-1).reshape(x.shape)
    angles = jnp.concatenate([angles, angles], axis=-1)
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(angles) + turned * jnp.sin(angles)


def attention(arch: Arch, w: Mapping[str, Any], u, positions, layout: str):
    t = u.shape[0]
    heads, groups = arch.num_attention_heads, arch.num_key_value_heads
    d = arch.hidden_size // heads
    eps = arch.norm_eps
    q = rms_norm((u @ w["q"]).reshape(t, heads, d), w["q_norm"], eps)
    k = rms_norm((u @ w["k"]).reshape(t, groups, d), w["k_norm"], eps)
    v = (u @ w["v"]).reshape(t, groups, d)
    q = rotary(q, positions, arch.rope_theta, layout)
    k = rotary(k, positions, arch.rope_theta, layout)
    causal = jnp.tril(jnp.ones((t, t), bool))
    out = []
    for h in range(heads):
        g = h // (heads // groups)
        scores = q[:, h] @ k[:, g].T * d ** -0.5
        weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        out.append(weights @ v[:, g])
    return jnp.concatenate(out, axis=-1) @ w["o"]


def gated(u, w_in, w_out):
    hidden = u @ w_in
    half = hidden.shape[-1] // 2
    return (jax.nn.silu(hidden[:, :half]) * hidden[:, half:]) @ w_out


def routed(arch: Arch, w: Mapping[str, Any], u, selection_bias: bool = True,
           bias_in_gates: bool = False, scoring: str = "sigmoid"):
    """``(the held experts' part, chosen (T, K))``."""
    logits = u @ w["router"]
    s = jax.nn.sigmoid(logits) if scoring == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    biased = s + w["expert_bias"]
    _, chosen = jax.lax.top_k(biased if selection_bias else s,
                              arch.num_experts_per_tok)
    gates = jnp.take_along_axis(biased if bias_in_gates else s, chosen,
                                axis=-1)
    if arch.norm_topk_prob:
        gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-6)
    gates = gates * arch.routed_scaling_factor
    out = jnp.zeros_like(u)
    for slot in range(arch.experts_held):
        gate = jnp.sum(jnp.where(chosen == arch.first_expert + slot,
                                 gates, 0.0), axis=-1)
        out = out + gate[:, None] * gated(u, w["experts_in"][slot],
                                          w["experts_out"][slot])
    return out, chosen


def token_states(arch: Arch, layer: Callable[[int], Mapping[str, Any]],
                 outer: Mapping[str, Any], ids, positions=None,
                 rotary_layout: str = "half", **routing) -> Tuple[Any, Any]:
    """One document's ``f`` (T, D) and every routed layer's choices (routed
    layers, T, K). ``layer(i)`` hands over layer ``i``'s float32 weights,
    one layer at a time; ``routing`` goes to :func:`routed`."""
    ids = jnp.asarray(ids)
    positions = np.arange(len(ids)) if positions is None else positions
    eps = arch.norm_eps
    x = outer["embed"][ids]
    chosen = []
    for i, kind in enumerate(arch.layer_types):
        w = layer(i)
        u = rms_norm(x, w["norm_op"], eps)
        x = x + (short_conv(w["op"], u) if kind == "conv"
                 else attention(arch, w["op"], u, positions, rotary_layout))
        u = rms_norm(x, w["norm_ffn"], eps)
        if i < arch.num_dense_layers:
            x = x + gated(u, w["mlp_in"], w["mlp_out"])
        else:
            out, picks = routed(arch, w, u, **routing)
            x = x + out
            chosen.append(picks)
    return rms_norm(x, outer["final_norm"], eps), jnp.stack(chosen)


def windows_of(n: int, window: int, step: int):
    """``[(start, end)]``: windows of ``window`` tokens every ``step``, the
    last one as short as the document leaves it."""
    count = 1 if n <= window else -(-(n - window) // step) + 1
    return [(i * step, min(i * step + window, n)) for i in range(count)]


def features(arch: Arch, layer, outer, ids, window: int, step: int
             ) -> Tuple[np.ndarray, np.ndarray]:
    """A document's features ``(windows, D)`` and expert counts ``(windows,
    routed layers, num_experts)``: every window is run as a document of its
    own, from position 0."""
    feats, counts = [], []
    for start, end in windows_of(len(ids), window, step):
        f, chosen = token_states(arch, layer, outer, ids[start:end])
        feats.append(np.asarray(f.mean(axis=0)))
        counts.append(np.asarray(jax.nn.one_hot(
            chosen, arch.num_experts).sum(axis=(1, 2))))
    return np.stack(feats), np.stack(counts).astype(np.int32)
