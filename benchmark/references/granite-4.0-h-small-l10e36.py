"""granite-4.0-h-small's forward pass in plain ``jax.numpy`` and float32,
the benchmark's own copy: matrix products at precision "highest", the
state-space recurrence token by token, the experts one by one, attention
dense, one window of the check item at a time and no packing. The equations,
with the ``config.json`` key behind every number, are in the docstring of
``video_features_tpu/reference/granite_hybrid.py``, which the program's tests
use; this file shares no code with it or with the model.

Of the program it imports the architecture's description and the loader
alone (``models/granite_hybrid.py Arch``, ``arch_from_config``,
``layer_weights``, ``outer_weights``: the seeded float32 weights before they
are rounded). It re-derives them layer by layer (one layer of the
configuration is 1.85 GB in float32; all ten do not fit beside the timed
tree) and computes from those. The tree it is handed, the one the window ran
in bfloat16, it only holds against them, leaf for leaf: the loader's weights
rounded once to the leaf's type, so a fault in the program's preparation of
its weights stops the check and is not shared by both sides.

``control`` is the same arithmetic with every matrix rounded to float8
(e4m3), the nearest precision under the configuration's bfloat16: put in the
program's place it has to fail ``checks/granite-4.0-h-small-l10e36.py
compare()``.

Departures from the published model: only the experts this chip holds
contribute (the configuration's cut); weights are seeded, not a checkpoint;
a feature is the mean of the final hidden states over a window.
"""
import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
#: the seed of the program's ``allow_random_weights`` (``assumed.weights``)
SEED = 0


def matmul(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight


@jax.jit
def recurrence(xs, b, c, step, a):
    """``S_t = exp(d_t a) S_{t-1} + d_t xs_t (x) B_t``, ``y_t = S_t C_t``
    from ``S = 0``, token by token: xs (T, H, P), b / c (T, N), step (T, H),
    a (H,)."""
    def token(state, inputs):
        x_t, b_t, c_t, d_t = inputs
        state = jnp.exp(d_t * a)[:, None, None] * state \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, jnp.einsum("hpn,n->hp", state, c_t, precision=HIGHEST)

    zero = jnp.zeros(xs.shape[1:] + b.shape[-1:], xs.dtype)
    return jax.lax.scan(token, zero, (xs, b, c, step))[1]


def mamba(arch, w, u):
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
    y = recurrence(xs, xbc[:, d_in:d_in + n], xbc[:, d_in + n:],
                   jax.nn.softplus(dt + w["dt_bias"]), -jnp.exp(w["A_log"]))
    y = (y + w["D"][:, None] * xs).reshape(t, d_in)
    return matmul(rms_norm(y * jax.nn.silu(z), w["norm"], arch.rms_norm_eps),
                  w["out_proj"])


def attention(arch, w, u):
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


def experts(arch, w, u):
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


def layer(arch, kind, w, x):
    """One layer on one window's residual stream ``x`` (T, D)."""
    u = rms_norm(x, w["norm1"], arch.rms_norm_eps)
    x = x + arch.residual_multiplier * (
        mamba if kind == "mamba" else attention)(arch, w["mixer"], u)
    out, chosen = experts(arch, w, rms_norm(x, w["norm2"], arch.rms_norm_eps))
    return x + arch.residual_multiplier * out, chosen


# -- the weights: re-derived, and the timed tree held against them -------------

def architecture(config):
    """The program's ``Arch`` for the configuration: its top-level keys are
    the published ``config.json``'s, with the router's width and the whole
    vocabulary under ``published`` and the chip's share in ``run_keys``."""
    from video_features_tpu.models.granite_hybrid import arch_from_config
    published = {**config, **{k: config["published"][k] for k in (
        "num_local_experts", "vocab_size")}}
    keys = config["run_keys"]
    return arch_from_config(published, keys["layer_shards"],
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
    from video_features_tpu.models.granite_hybrid import (layer_weights,
                                                          outer_weights)
    arch = architecture(config)
    window = int(config["unit"]["window"])
    ids = np.fromfile(check_path, dtype="<i4")
    assert 0 <= ids.min() and ids.max() < arch.vocab_held, check_path
    spans = [(s, min(s + window, len(ids)))
             for s in range(0, len(ids), window)]

    def matrices(tree):
        return jax.tree_util.tree_map(
            lambda a: rounded(a) if a.ndim >= 2 else a, tree)

    outer = matrices(held_against(
        {k: params[k] for k in ("embed", "final_norm")},
        outer_weights(arch, SEED), "outer"))
    xs = [arch.embedding_multiplier * outer["embed"][jnp.asarray(ids[s:e])]
          for s, e in spans]
    counts = np.zeros((len(spans), len(arch.layer_types),
                       arch.num_local_experts), np.int32)
    # layers outside, windows inside: a layer's float32 weights are drawn
    # once and dropped before the next layer's arrive
    for i, kind in enumerate(arch.layer_types):
        w = matrices(held_against(params["layers"][i],
                                  layer_weights(arch, SEED, i),
                                  f"layers[{i}]"))
        for j, x in enumerate(xs):
            xs[j], chosen = layer(arch, kind, w, x)
            counts[j, i] = np.bincount(np.asarray(chosen).ravel(),
                                       minlength=arch.num_local_experts)
        del w
    feats = np.stack([np.asarray(rms_norm(
        x, outer["final_norm"], arch.rms_norm_eps).mean(axis=0)) for x in xs])
    return {config["run_keys"]["feature_type"]: feats.astype(np.float32),
            "expert_tokens": counts}


def features(params, config, check_path):
    return window_features(params, config, check_path, lambda a: a)


def control(params, config, check_path):
    return window_features(
        params, config, check_path,
        lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32))
