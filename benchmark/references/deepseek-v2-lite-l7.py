"""DeepSeek-V2-Lite's forward pass in plain ``jax.numpy`` and float32, the
benchmark's own copy: matrix products at precision "highest" (set by the
caller), attention dense with the queries in blocks (all 16 heads' scores of
256 queries against 16,384 keys are 268 MB in float32, and a plain softmax
keeps four or five such arrays alive), the experts one by one on every token, one window of the check item at a time as a document
of its own: no packing, no segment mask, positions 0 .. L - 1. The
equations, with the ``config.json`` key behind every number, are in the
docstring of ``video_features_tpu/reference/deepseek_v2.py``, which the
program's tests use; this file shares no code with it or with the model.

Of the program it imports the architecture's description and the loader
alone (``models/deepseek_v2.py arch_from_config``, ``layer_weights``,
``outer_weights``: the seeded float32 weights before they are rounded). It
re-derives them layer by layer (an expert layer of the configuration is 2.34
GB in float32; all seven do not fit beside the timed tree) and computes from
those. The tree it is handed, the one the window ran in bfloat16, it only
holds against them, leaf for leaf: the loader's weights rounded once to the
leaf's type, so a fault in the program's preparation of its weights stops
the check and is not shared by both sides.

``control`` is the same arithmetic with every matrix rounded to float8
(e4m3), the nearest precision under the configuration's bfloat16: put in the
program's place it has to fail ``checks/deepseek-v2-lite-l7.py compare()``.
A matrix is rounded where it is used (``r`` below: the identity, or through
float8), never the layer's tree at once: beside the timed tree (7.6 GB), the
timed program's reserved temporaries and the float32 layer there is no room
for a second copy of the layer.

Departures from the published model: weights are seeded, not a checkpoint; the
cut model ends in its final RMSNorm and a feature is the mean of the final
hidden states over a window; MLA is computed expanded (keys and values
up-projected per head), which is the published code's own form.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

#: the seed of the program's ``allow_random_weights`` (``assumed.weights``)
SEED = 0
#: queries a block of the dense attention
QUERY_BLOCK = 256


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight


def mscale(factor, a):
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def inv_freq(config):
    """YaRN: ``theta^(-2i/d)`` kept for the pairs that turn more than
    ``beta_fast`` times over the original length, divided by ``factor`` for
    those that turn fewer than ``beta_slow`` times, blended between."""
    rope = config["rope_scaling"]
    d, theta = int(config["qk_rope_head_dim"]), float(config["rope_theta"])
    original = int(rope["original_max_position_embeddings"])

    def pair(turns):
        return d * math.log(original / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair(rope["beta_fast"])), 0)
    high = min(math.ceil(pair(rope["beta_slow"])), d - 1)
    i = np.arange(d // 2, dtype=np.float64)
    f = theta ** (-2 * i / d)
    ramp = np.clip((i - low) / ((high + 0.001 if high == low else high)
                                - low), 0, 1)
    return f * (1 - ramp) + f / float(rope["factor"]) * ramp


def rotary(config, x):
    """``x`` (T, ..., d) at positions 0 .. T - 1, in the published layout:
    pairs (2j, 2j + 1) de-interleaved, then ``x cos + rotate_half(x) sin``."""
    rope = config["rope_scaling"]
    half = int(config["qk_rope_head_dim"]) // 2
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq(config), jnp.float32)[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    angles = angles.reshape(angles.shape[:1] + (1,) * (x.ndim - 2)
                            + angles.shape[1:])
    m = mscale(rope["factor"], rope["mscale"]) \
        / mscale(rope["factor"], rope["mscale_all_dim"])
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * (jnp.cos(angles) * m) + turned * (jnp.sin(angles) * m)


def attention(config, w, u, r):
    t = u.shape[0]
    heads, nope, rope, v_dim, rank = (
        int(config[k]) for k in ("num_attention_heads", "qk_nope_head_dim",
                                 "qk_rope_head_dim", "v_head_dim",
                                 "kv_lora_rank"))
    scaling = config["rope_scaling"]
    scale = (nope + rope) ** -0.5 \
        * mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    q = (u @ r(w["q"])).reshape(t, heads, nope + rope)
    kv_a = u @ r(w["kv_a"])
    latent = rms_norm(kv_a[:, :rank], w["kv_a_norm"], config["rms_norm_eps"])
    k_pe = rotary(config, kv_a[:, rank:])                       # (T, rope)
    kv = (latent @ r(w["kv_b"])).reshape(t, heads, nope + v_dim)
    q_nope, q_pe = q[..., :nope], rotary(config, q[..., nope:])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    out = []
    for start in range(0, t, QUERY_BLOCK):
        end = min(start + QUERY_BLOCK, t)
        scores = (jnp.einsum("qhd,khd->hqk", q_nope[start:end], k_nope)
                  + jnp.einsum("qhd,kd->hqk", q_pe[start:end], k_pe)) * scale
        causal = jnp.arange(start, end)[:, None] >= jnp.arange(t)[None, :]
        weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", weights, v))
    return jnp.concatenate(out).reshape(t, heads * v_dim) @ r(w["o"])


def gated(u, w_in, w_out, r):
    hidden = u @ r(w_in)
    half = hidden.shape[-1] // 2
    return (jax.nn.silu(hidden[:, :half]) * hidden[:, half:]) @ r(w_out)


def experts(config, w, u, r):
    p = jax.nn.softmax(u @ r(w["router"]), axis=-1)
    gates, chosen = jax.lax.top_k(p, int(config["num_experts_per_tok"]))
    if config["norm_topk_prob"]:
        gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-20)
    gates = gates * config["routed_scaling_factor"]
    out = gated(u, w["shared_in"], w["shared_out"], r)
    for e in range(w["experts_in"].shape[0]):
        gate = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)
        out = out + gate[:, None] * gated(u, w["experts_in"][e],
                                          w["experts_out"][e], r)
    return out, chosen


def layer(config, w, x, r):
    """One layer on one window's residual stream ``x`` (T, D), every matrix
    through ``r`` where it is used; the routed choices (T, K), or None for a
    dense layer."""
    eps = config["rms_norm_eps"]
    x = x + attention(config, w["attn"], rms_norm(x, w["norm1"], eps), r)
    u = rms_norm(x, w["norm2"], eps)
    if "router" not in w:
        return x + gated(u, w["mlp_in"], w["mlp_out"], r), None
    out, chosen = experts(config, w, u, r)
    return x + out, chosen


# -- the weights: re-derived, and the timed tree held against them -------------

def architecture(config):
    """The program's ``Arch`` for the configuration: its top-level keys are
    the published ``config.json``'s, the chip's share is in ``run_keys``."""
    from video_features_tpu.models.deepseek_v2 import arch_from_config
    keys = config["run_keys"]
    return arch_from_config(config, keys["layer_shards"],
                            keys["layer_shard_rank"])


def held_against(ran, unrounded, where):
    """The timed tree's part is the loader's, rounded once to each leaf's
    type; returns the unrounded part."""
    timed = jax.tree_util.tree_leaves_with_path(ran)
    whole = jax.tree_util.tree_leaves_with_path(unrounded)
    assert [p for p, _ in timed] == [p for p, _ in whole], \
        f"{where}: another tree"
    for (path, leaf), (_, full) in zip(timed, whole):
        assert bool(jnp.array_equal(leaf, full.astype(leaf.dtype))), \
            f"{where}{jax.tree_util.keystr(path)}: not the loader's, " \
            "rounded once"
    return unrounded


def window_features(params, config, check_path, rounded):
    from video_features_tpu.models.deepseek_v2 import (layer_weights,
                                                       outer_weights)
    arch = architecture(config)
    window = int(config["unit"]["window"])
    ids = np.fromfile(check_path, dtype="<i4")
    assert 0 <= ids.min() and ids.max() < arch.vocab_held, check_path
    spans = [(s, min(s + window, len(ids)))
             for s in range(0, len(ids), window)]

    outer = held_against({k: params[k] for k in ("embed", "final_norm")},
                         outer_weights(arch, SEED), "outer")
    xs = [rounded(outer["embed"][jnp.asarray(ids[s:e])]) for s, e in spans]
    counts = []
    # layers outside, windows inside: a layer's float32 weights are drawn
    # once and dropped before the next layer's arrive
    for i in range(arch.num_hidden_layers):
        w = held_against(params["layers"][i], layer_weights(arch, SEED, i),
                         f"layers[{i}]")
        routed = []
        for j, x in enumerate(xs):
            xs[j], chosen = layer(config, w, x, rounded)
            if chosen is not None:
                routed.append(np.bincount(np.asarray(chosen).ravel(),
                                          minlength=arch.n_routed_experts))
        if routed:
            counts.append(routed)
        del w
    feats = np.stack([np.asarray(rms_norm(
        x, outer["final_norm"], config["rms_norm_eps"]).mean(axis=0))
        for x in xs])
    return {config["run_keys"]["feature_type"]: feats.astype(np.float32),
            # (routed layers, windows, experts) -> windows first
            "expert_tokens": np.asarray(counts, np.int32).transpose(1, 0, 2)}


def features(params, config, check_path):
    return window_features(params, config, check_path, lambda a: a)


def control(params, config, check_path):
    return window_features(
        params, config, check_path,
        lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32))
