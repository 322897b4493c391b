"""granite-4.0-h-small's forward pass, plainly: ``jax.numpy``, float32,
matrix products at precision "highest", the state-space recurrence token by
token, the experts one by one, attention dense. No packing: it is given one
document (or one window of one) at a time. It shares no arithmetic with
``models/granite_hybrid.py``; of the program it takes the architecture's
description (``Arch``) and the seeded weights (``layer_weights``,
``outer_weights``), unrounded.

With tokens ``t`` of one document (``config.json`` keys in brackets)::

    x = embedding_multiplier * E[id_t]
    for each layer:   x += residual_multiplier * Mixer(RMSNorm(x))
                      u  = RMSNorm(x)
                      x += residual_multiplier * (Experts(u) + Shared(u))
    f = RMSNorm(x);   logits = f E^T / logits_scaling          (tied)

Mamba-2 mixer (``mamba_*``): ``[z | xBC | dt] = u W_in``; ``xBC =
silu(conv1d_causal(xBC) + b)`` (depthwise, ``mamba_d_conv`` taps, zeros
before the first token); ``[xs | B | C] = xBC`` with ``xs`` as heads of
``mamba_d_head``; ``d = softplus(dt + dt_bias)``, ``a = -exp(A_log)``; per
head ``S_t = exp(d_t a) S_{t-1} + d_t xs_t (x) B_t`` from ``S = 0``, ``y_t =
S_t C_t + D xs_t``; ``out = RMSNorm(y * silu(z)) W_out``. Attention: grouped
queries (``num_attention_heads`` over ``num_key_value_heads``), no position
embedding, scores times ``attention_multiplier``, causal. Experts: router
logits over all ``num_local_experts``, the ``num_experts_per_tok`` largest,
gates = softmax over those; an expert is ``(silu(u W[:, :I]) * (u W[:, I:]))
V``; shared expert the same form on every token.

Departures from the published model, each on purpose: (1) only the experts
``first_expert`` .. ``+ experts_held`` contribute (this chip's share of a
layer; what the others would add is left out, and that partial sum goes on),
and logits are over the held rows of ``E``; (2) weights are seeded, not a
checkpoint; (3) features are the mean of ``f`` over a window's tokens, the
system's own definition, not the model's.
"""
from __future__ import annotations

from typing import Any, Callable, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.granite_hybrid import Arch

HIGHEST = jax.lax.Precision.HIGHEST


def matmul(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight


@jax.jit
def recurrence(xs, b, c, step, a):
    """``S_t = exp(d_t a) S_{t-1} + d_t xs_t (x) B_t``, ``y_t = S_t C_t``,
    token by token from ``S = 0``: xs (T, H, P), b / c (T, N), step (T, H),
    a (H,). (Compiled once for a length: it is called for every layer.)"""
    def token(state, inputs):
        x_t, b_t, c_t, d_t = inputs
        state = jnp.exp(d_t * a)[:, None, None] * state \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, jnp.einsum("hpn,n->hp", state, c_t, precision=HIGHEST)

    zero = jnp.zeros(xs.shape[1:] + b.shape[-1:], xs.dtype)
    return jax.lax.scan(token, zero, (xs, b, c, step))[1]


def mamba(arch: Arch, w: Mapping[str, Any], u):
    """``u`` (T, D) -> (T, D)."""
    t = u.shape[0]
    h, p, n, d_in = (arch.mamba_n_heads, arch.mamba_d_head,
                     arch.mamba_d_state, arch.mamba_d_inner)
    zxbcdt = matmul(u, w["in_proj"])
    z, xbc, dt = (zxbcdt[:, :d_in], zxbcdt[:, d_in:d_in + arch.conv_dim],
                  zxbcdt[:, d_in + arch.conv_dim:])
    k = arch.mamba_d_conv
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(w["conv_b"] + sum(
        padded[j:j + t] * w["conv_w"][j] for j in range(k)))
    xs = xbc[:, :d_in].reshape(t, h, p)
    b, c = xbc[:, d_in:d_in + n], xbc[:, d_in + n:]
    step = jax.nn.softplus(dt + w["dt_bias"])                   # (T, H)
    a = -jnp.exp(w["A_log"])

    y = recurrence(xs, b, c, step, a)
    y = (y + w["D"][:, None] * xs).reshape(t, d_in)
    return matmul(rms_norm(y * jax.nn.silu(z), w["norm"], arch.rms_norm_eps),
                  w["out_proj"])


def attention(arch: Arch, w: Mapping[str, Any], u):
    t = u.shape[0]
    heads, kv, hd = (arch.num_attention_heads, arch.num_key_value_heads,
                     arch.head_dim)
    q = matmul(u, w["q"]).reshape(t, heads, hd)
    k = matmul(u, w["k"]).reshape(t, kv, hd)
    v = matmul(u, w["v"]).reshape(t, kv, hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    out = []
    for head in range(heads):
        shared = head // (heads // kv)
        scores = matmul(q[:, head], k[:, shared].T) * arch.attention_multiplier
        weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        out.append(matmul(weights, v[:, shared]))
    return matmul(jnp.concatenate(out, axis=-1), w["o"])


def gated(u, w_in, w_out):
    hidden = matmul(u, w_in)
    half = hidden.shape[-1] // 2
    return matmul(jax.nn.silu(hidden[:, :half]) * hidden[:, half:], w_out)


def experts(arch: Arch, w: Mapping[str, Any], u):
    """``(held experts' part + shared expert, chosen (T, K))``."""
    logits = matmul(u, w["router"])
    top, chosen = jax.lax.top_k(logits, arch.num_experts_per_tok)
    gates = jax.nn.softmax(top, axis=-1)
    out = gated(u, w["shared_in"], w["shared_out"])
    for slot in range(arch.experts_held):
        gate = jnp.sum(jnp.where(chosen == arch.first_expert + slot,
                                 gates, 0.0), axis=-1)
        out = out + gate[:, None] * gated(u, w["experts_in"][slot],
                                          w["experts_out"][slot])
    return out, chosen


def token_states(arch: Arch, layer: Callable[[int], Mapping[str, Any]],
                 outer: Mapping[str, Any], ids) -> Tuple[Any, Any]:
    """One document's ``f`` (T, D) and every layer's router choices
    (layers, T, K). ``layer(i)`` hands over layer ``i``'s float32 weights,
    one layer at a time (all of them need not fit at once)."""
    ids = jnp.asarray(ids)
    x = arch.embedding_multiplier * outer["embed"][ids]
    chosen = []
    for i, kind in enumerate(arch.layer_types):
        w = layer(i)
        u = rms_norm(x, w["norm1"], arch.rms_norm_eps)
        x = x + arch.residual_multiplier * (
            mamba if kind == "mamba" else attention)(arch, w["mixer"], u)
        out, picks = experts(arch, w, rms_norm(x, w["norm2"],
                                               arch.rms_norm_eps))
        x = x + arch.residual_multiplier * out
        chosen.append(picks)
    return rms_norm(x, outer["final_norm"], arch.rms_norm_eps), \
        jnp.stack(chosen)


def logits(arch: Arch, outer: Mapping[str, Any], f):
    return matmul(f, outer["embed"].T) / arch.logits_scaling


def windows_of(n: int, window: int, step: int):
    """``[(start, end)]``: windows of ``window`` tokens every ``step``, the
    last one as short as the document leaves it; ``ceil(n / window)`` of
    them where ``step == window``."""
    count = 1 if n <= window else -(-(n - window) // step) + 1
    return [(i * step, min(i * step + window, n)) for i in range(count)]


def features(arch: Arch, layer, outer, ids, window: int, step: int
             ) -> Tuple[np.ndarray, np.ndarray]:
    """A document's features ``(windows, D)`` and expert counts ``(windows,
    layers, num_local_experts)``: every window is run as a document of its
    own."""
    feats, counts = [], []
    for start, end in windows_of(len(ids), window, step):
        f, chosen = token_states(arch, layer, outer, ids[start:end])
        feats.append(np.asarray(f.mean(axis=0)))
        counts.append(np.asarray(jax.nn.one_hot(
            chosen, arch.num_local_experts).sum(axis=(1, 2))))
    return np.stack(feats), np.stack(counts).astype(np.int32)
