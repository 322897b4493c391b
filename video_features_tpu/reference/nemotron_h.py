"""NVIDIA-Nemotron-3-Super's forward pass (``model_type`` nemotron_h),
plainly: ``jax.numpy``, float32, matrix products at precision "highest" (the
caller sets ``jax.default_matmul_precision("highest")``), the state-space
recurrence token by token, attention dense and head by head, the chosen
experts in a Python loop. No packing: it is given one document (or one
window of one) at a time, so there is no segment mask. It shares no
arithmetic with ``models/nemotron_h.py``; of the program it takes the
architecture's description (``Arch``) and the seeded weights
(``layer_weights``, ``outer_weights``), unrounded.

With tokens ``t`` of one document (``config.json`` keys in brackets; ``D``
``hidden_size``, no bias anywhere but the convolution's)::

    RMSNorm(x) = x rsqrt(mean(x^2) + layer_norm_epsilon) w
    x = E[id_t]
    for each layer i:  x = x + Block_i(RMSNorm(x))   Block by the pattern
    f = RMSNorm(x)

``M`` (``mamba_num_heads`` H of ``mamba_head_dim`` P, state
``ssm_state_size`` N, ``n_groups`` G, ``conv_kernel`` L): ``[z | xBC | dt]
= u W_in``; ``xBC = silu(sum_j k_j xBC_{t-L+1+j} + b)``, a tap before the
document's first token reading zero; ``xBC = [x (H P) | B (G N) | C (G
N)]``; ``dt = softplus(dt + dt_bias)``, ``a = -exp(A_log)``; per head ``h``,
reading group ``g = h // (H / G)``: ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t
B_{t,g}^T`` (S is P x N, zero before the first token), ``y_t = S_t C_{t,g} +
D x_t``; ``y = GroupRMSNorm(y * silu(z))``, the RMS taken over each of G
groups of ``H P / G`` channels; ``y W_out``.

``*`` (``num_attention_heads`` of ``head_dim`` d over
``num_key_value_heads``): ``q = u W_q``, ``k = u W_k``, ``v = u W_v``, no
position embedding; query head ``h`` reads key and value head ``h // (heads
/ kv heads)``; ``softmax(q k^T d^-0.5)`` causal; ``concat_h(o_h) W_o``.

``E``: ``s = sigmoid(u W_r)`` over all ``n_routed_experts``; ``chosen =
top_K(s + e_score_correction_bias)`` (K ``num_experts_per_tok``); ``g =
s[chosen] / (sum s[chosen] + 1e-20)`` (``norm_topk_prob``) ``*
routed_scaling_factor``; ``l = u W_down`` (``moe_latent_size``); ``routed =
(sum_{e in chosen} g_e relu(l U_e)^2 V_e) W_up``; ``shared = relu(u S_in)^2
S_out`` (``moe_shared_expert_intermediate_size``); ``Block = routed +
shared``.

Departures from the published code, each on purpose: (1) only the experts
``first_expert`` .. ``+ experts_held`` contribute (this chip's share of a
layer) and only the held rows of the vocabulary exist; (2) weights are
seeded, not a checkpoint: the norms are ones, ``A_log = log(1 .. H)``, ``D``
ones, ``dt_bias`` the published initialisation's, and
``e_score_correction_bias``, a buffer a checkpoint carries, is drawn
normal(0, 0.05); (3) the cut model ends in its final RMSNorm (the head and
the multi-token-prediction module are on the last pipeline stage) and
features are the mean of ``f`` over a window's tokens, the system's own
definition; (4) the router's matrix is a matrix like the others: where the
program serves in bfloat16 it is rounded with them (the published router
keeps it float32).

``selection_bias``, ``norm_groups``, ``bc_groups`` and ``activation`` can
be changed so that the tests can show that the comparison notices each.
"""
from __future__ import annotations

from typing import Any, Callable, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.nemotron_h import Arch


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight


def relu2(x):
    return jnp.maximum(x, 0.0) ** 2


def mamba(arch: Arch, w: Mapping[str, Any], u, norm_groups=None,
          bc_groups: str = "consecutive"):
    """``u`` (T, D) of one document. ``bc_groups`` ``"interleaved"`` has
    head ``h`` read group ``h % G`` (for the tests)."""
    t = u.shape[0]
    h, p, n = arch.mamba_num_heads, arch.mamba_head_dim, arch.ssm_state_size
    g, d_in = arch.n_groups, arch.d_inner
    proj = u @ w["in_proj"]
    z, xbc, dt = (proj[:, :d_in], proj[:, d_in:d_in + arch.conv_dim],
                  proj[:, d_in + arch.conv_dim:])
    taps = w["conv_w"]
    length = taps.shape[0]
    padded = jnp.concatenate([jnp.zeros((length - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(sum(taps[j] * padded[j:j + t] for j in range(length))
                      + w["conv_b"])
    x = xbc[:, :d_in].reshape(t, h, p)
    b = xbc[:, d_in:d_in + g * n].reshape(t, g, n)
    c = xbc[:, d_in + g * n:].reshape(t, g, n)
    group = (np.arange(h) // (h // g) if bc_groups == "consecutive"
             else np.arange(h) % g)
    b, c = b[:, group], c[:, group]                             # (T, H, N)
    dt = jax.nn.softplus(dt + w["dt_bias"])                     # (T, H)
    a = -jnp.exp(w["A_log"])

    def step(state, inputs):
        x_t, b_t, c_t, dt_t = inputs
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((h, p, n)), (x, b, c, dt))
    y = (y + x * w["D"][:, None]).reshape(t, d_in) * jax.nn.silu(z)
    parts = y.reshape(t, norm_groups or g, -1)
    y = (parts * jax.lax.rsqrt(jnp.mean(parts * parts, axis=-1,
                                        keepdims=True)
                               + arch.layer_norm_epsilon)).reshape(t, d_in)
    return (y * w["norm"]) @ w["out_proj"]


def attention(arch: Arch, w: Mapping[str, Any], u):
    t = u.shape[0]
    heads, groups, d = (arch.num_attention_heads, arch.num_key_value_heads,
                        arch.head_dim)
    q = (u @ w["q"]).reshape(t, heads, d)
    k = (u @ w["k"]).reshape(t, groups, d)
    v = (u @ w["v"]).reshape(t, groups, d)
    causal = jnp.tril(jnp.ones((t, t), bool))
    out = []
    for head in range(heads):
        kv = head // (heads // groups)
        scores = q[:, head] @ k[:, kv].T * d ** -0.5
        weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        out.append(weights @ v[:, kv])
    return jnp.concatenate(out, axis=-1) @ w["o"]


def route(arch: Arch, w: Mapping[str, Any], u, selection_bias: bool = True):
    """``(gates, chosen)``, each (T, K)."""
    s = jax.nn.sigmoid(u @ w["router"])
    _, chosen = jax.lax.top_k(s + w["selection_bias"] if selection_bias
                              else s, arch.num_experts_per_tok)
    gates = jnp.take_along_axis(s, chosen, axis=-1)
    if arch.norm_topk_prob:
        gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-20)
    return gates * arch.routed_scaling_factor, chosen


def routed(arch: Arch, w: Mapping[str, Any], u, selection_bias: bool = True,
           activation=relu2):
    """``(the held experts' part, projected back to D, chosen (T, K))``."""
    gates, chosen = route(arch, w, u, selection_bias)
    latent = u @ w["latent_down"]
    out = jnp.zeros_like(latent)
    for slot in range(arch.experts_held):
        gate = jnp.sum(jnp.where(chosen == arch.first_expert + slot,
                                 gates, 0.0), axis=-1)
        out = out + gate[:, None] * (activation(
            latent @ w["experts_in"][slot]) @ w["experts_out"][slot])
    return out @ w["latent_up"], chosen


def shared(w: Mapping[str, Any], u, activation=relu2):
    return activation(u @ w["shared_in"]) @ w["shared_out"]


def token_states(arch: Arch, layer: Callable[[int], Mapping[str, Any]],
                 outer: Mapping[str, Any], ids, norm_groups=None,
                 bc_groups: str = "consecutive", selection_bias: bool = True,
                 activation=relu2) -> Tuple[Any, Any]:
    """One document's ``f`` (T, D) and every E layer's choices (E layers,
    T, K). ``layer(i)`` hands over layer ``i``'s float32 weights, one layer
    at a time."""
    ids = jnp.asarray(ids)
    eps = arch.layer_norm_epsilon
    x = outer["embed"][ids]
    chosen = []
    for i, kind in enumerate(arch.layer_kinds):
        w = layer(i)
        u = rms_norm(x, w["pre_norm"], eps)
        if kind == "mamba":
            x = x + mamba(arch, w, u, norm_groups, bc_groups)
        elif kind == "attn":
            x = x + attention(arch, w, u)
        else:
            out, picks = routed(arch, w, u, selection_bias, activation)
            x = x + out + shared(w, u, activation)
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
    E layers, n_routed_experts)``: every window is run as a document of its
    own."""
    feats, counts = [], []
    for start, end in windows_of(len(ids), window, step):
        f, chosen = token_states(arch, layer, outer, ids[start:end])
        feats.append(np.asarray(f.mean(axis=0)))
        counts.append(np.asarray(jax.nn.one_hot(
            chosen, arch.n_routed_experts).sum(axis=(1, 2))))
    return np.stack(feats), np.stack(counts).astype(np.int32)
