"""LFM2-8B-A1B's forward pass in plain ``jax.numpy`` and float32, the
benchmark's own copy: matrix products at precision "highest" (set by the
caller), the convolution's taps one by one, attention dense with the queries
in blocks (all 32 heads' scores of 128 queries against 16,384 keys are 268 MB
in float32), the experts one by one on every token, one window of the check
item at a time as a document of its own: no packing, no segment mask,
positions 0 .. L - 1. The equations, with the ``config.json`` key behind
every number, are in the docstring of
``video_features_tpu/reference/lfm2_moe.py``, which the program's tests use;
this file shares no code with it or with the model.

Of the program it imports the architecture's description and the loader
alone (``models/lfm2_moe.py arch_from_config``, ``layer_weights``,
``outer_weights``: the seeded float32 weights before they are rounded). It
re-derives them layer by layer (a routed conv layer of the configuration is
1.48 GB in float32; all twelve do not fit beside the timed tree) and
computes from those. The tree it is handed, the one the window ran in
bfloat16, it only holds against them, leaf for leaf: the loader's weights
rounded once to the leaf's type, so a fault in the program's preparation of
its weights stops the check and is not shared by both sides.

``control`` is the same arithmetic with every matrix rounded to float8
(e4m3), the nearest precision under the configuration's bfloat16: put in the
program's place it has to fail ``checks/lfm2-8b-a1b-l12.py compare()``. A
matrix is rounded where it is used (``r`` below: the identity, or through
float8), never the layer's tree at once: beside the timed tree (7.86 GB),
the timed program's reserved temporaries and the float32 layer there is no
room for a second copy of the layer.

Departures from the published model: weights are seeded, not a checkpoint
(``expert_bias`` too, normal(0, 0.05)); the cut model ends in its final
RMSNorm and a feature is the mean of the final hidden states over a window.
"""
import jax
import jax.numpy as jnp
import numpy as np

#: the seed of the program's ``allow_random_weights`` (``assumed.weights``)
SEED = 0
#: queries a block of the dense attention
QUERY_BLOCK = 128


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight


def short_conv(w, u, r):
    """``[B | C | x] = u W_in``; ``C * sum_j k_j (B * x)_{t - L + 1 + j}``,
    a tap before the window's first token reading zero; ``W_out``."""
    b, c, x = jnp.split(u @ r(w["in_proj"]), 3, axis=-1)
    taps = r(w["conv_w"])
    length, t = taps.shape[0], u.shape[0]
    gated = jnp.concatenate([jnp.zeros((length - 1, u.shape[1])), b * x])
    y = sum(taps[j] * gated[j:j + t] for j in range(length))
    return (c * y) @ r(w["out_proj"])


def rotary(x, theta):
    """``x`` (T, heads, d) at positions 0 .. T - 1, the ``rotate_half``
    layout: channel ``j`` with channel ``j + d / 2``."""
    d = x.shape[-1]
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(theta ** (-np.arange(0, d, 2) / d), jnp.float32)
    angles = jnp.concatenate([angles, angles], axis=-1)[:, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(angles) + turned * jnp.sin(angles)


def attention(config, w, u, r):
    t = u.shape[0]
    heads, groups = (int(config["num_attention_heads"]),
                     int(config["num_key_value_heads"]))
    d = int(config["hidden_size"]) // heads
    eps, theta = config["norm_eps"], float(config["rope_theta"])
    q = rms_norm((u @ r(w["q"])).reshape(t, heads, d), w["q_norm"], eps)
    k = rms_norm((u @ r(w["k"])).reshape(t, groups, d), w["k_norm"], eps)
    v = (u @ r(w["v"])).reshape(t, groups, d)
    q, k = rotary(q, theta), rotary(k, theta)
    # query head h reads key and value head h // (heads / groups)
    k, v = (jnp.repeat(a, heads // groups, axis=1) for a in (k, v))
    out = []
    for start in range(0, t, QUERY_BLOCK):
        end = min(start + QUERY_BLOCK, t)
        scores = jnp.einsum("qhd,khd->hqk", q[start:end], k) * d ** -0.5
        causal = jnp.arange(start, end)[:, None] >= jnp.arange(t)[None, :]
        weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", weights, v))
    return jnp.concatenate(out).reshape(t, heads * d) @ r(w["o"])


def gated(u, w_in, w_out, r):
    hidden = u @ r(w_in)
    half = hidden.shape[-1] // 2
    return (jax.nn.silu(hidden[:, :half]) * hidden[:, half:]) @ r(w_out)


def experts(config, w, u, r):
    """Sigmoid scores; the top ``num_experts_per_tok`` of scores plus
    ``expert_bias`` chosen; the chosen scores, renormalised, as gates."""
    s = jax.nn.sigmoid(u @ r(w["router"]))
    _, chosen = jax.lax.top_k(s + w["expert_bias"],
                              int(config["num_experts_per_tok"]))
    gates = jnp.take_along_axis(s, chosen, axis=-1)
    if config["norm_topk_prob"]:
        gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-6)
    gates = gates * config["routed_scaling_factor"]
    out = jnp.zeros_like(u)
    for e in range(w["experts_in"].shape[0]):
        gate = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)
        out = out + gate[:, None] * gated(u, w["experts_in"][e],
                                          w["experts_out"][e], r)
    return out, chosen


def layer(config, w, x, index, r):
    """Layer ``index`` on one window's residual stream ``x`` (T, D), every
    matrix through ``r`` where it is used; the routed choices (T, K), or
    None for a dense layer."""
    eps = config["norm_eps"]
    u = rms_norm(x, w["norm_op"], eps)
    x = x + (short_conv(w["op"], u, r)
             if config["layer_types"][index] == "conv"
             else attention(config, w["op"], u, r))
    u = rms_norm(x, w["norm_ffn"], eps)
    if index < int(config["num_dense_layers"]):
        return x + gated(u, w["mlp_in"], w["mlp_out"], r), None
    out, chosen = experts(config, w, u, r)
    return x + out, chosen


# -- the weights: re-derived, and the timed tree held against them -----------

def architecture(config):
    """The program's ``Arch`` for the configuration: its top-level keys are
    the published ``config.json``'s, the chip's share is in ``run_keys``."""
    from video_features_tpu.models.lfm2_moe import arch_from_config
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
    from video_features_tpu.models.lfm2_moe import (layer_weights,
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
    for i in range(int(config["num_hidden_layers"])):
        w = held_against(params["layers"][i], layer_weights(arch, SEED, i),
                         f"layers[{i}]")
        routed = []
        for j, x in enumerate(xs):
            xs[j], chosen = layer(config, w, x, i, rounded)
            if chosen is not None:
                routed.append(np.bincount(
                    np.asarray(chosen).ravel(),
                    minlength=int(config["num_experts"])))
        if routed:
            counts.append(routed)
        del w
    feats = np.stack([np.asarray(rms_norm(
        x, outer["final_norm"], config["norm_eps"]).mean(axis=0))
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
