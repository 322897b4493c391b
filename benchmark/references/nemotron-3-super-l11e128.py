"""NVIDIA-Nemotron-3-Super's forward pass in plain ``jax.numpy`` and float32,
the benchmark's own copy: matrix products at precision "highest" (set by the
caller), the convolution's taps one by one, the state-space recurrence token
by token (a ``lax.scan`` over the window's tokens, no chunks), attention
dense with the queries in blocks (all 32 heads' scores of 128 queries
against 16,384 keys are 268 MB in float32), the held experts one by one on
every token, one window of the check item at a time as a document of its
own: no packing, no segment mask. The equations, with the ``config.json``
key behind every number, are in the docstring of
``video_features_tpu/reference/nemotron_h.py``, which the program's tests
use; this file shares no code with it or with the model.

Of the program it imports the architecture's description and the loader
alone (``models/nemotron_h.py arch_from_config``, ``layer_weights``,
``outer_weights``: the seeded float32 weights before they are rounded). It
re-derives them layer by layer, and an E layer's held experts in blocks of
:data:`EXPERT_BLOCK` (all 128 are 2.82 GB in float32; beside the timed tree,
9.03 GB, and a Mamba layer's float32 activations there is no room for them
at once): expert ``e`` is drawn under its own key, so a block is drawn as
the share of a chip that holds those experts alone. The tree it is handed,
the one the window ran in bfloat16, it only holds against them, leaf for
leaf: the loader's weights rounded once to the leaf's type, so a fault in
the program's preparation of its weights stops the check and is not shared
by both sides.

``control`` is the same arithmetic with every matrix rounded to float8
(e4m3), the nearest precision under the configuration's bfloat16: put in the
program's place it has to fail ``checks/nemotron-3-super-l11e128.py
compare()``. A matrix is rounded where it is used (``r`` below: the
identity, or through float8), never the layer's tree at once.

Departures from the published model: weights are seeded, not a checkpoint
(``e_score_correction_bias`` too, normal(0, 0.05)); only this chip's experts
and vocabulary rows exist; the cut model ends in its final RMSNorm and a
feature is the mean of the final hidden states over a window.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

#: the seed of the program's ``allow_random_weights`` (``assumed.weights``)
SEED = 0
#: queries a block of the dense attention
QUERY_BLOCK = 128
#: held experts drawn, checked and applied at a time
EXPERT_BLOCK = 32


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight


def relu2(x):
    return jnp.maximum(x, 0.0) ** 2


def mamba(config, w, u, r):
    """``[z | xBC | dt] = u W_in``; ``xBC = silu(taps + bias)``, a tap
    before the window's first token reading zero; per head ``h`` and its
    group ``h // (H / G)``: ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``,
    ``y_t = S_t C_t + D x_t``; the RMS of ``y * silu(z)`` per group of
    channels; ``W_out``."""
    t = u.shape[0]
    h, p, n = (int(config["mamba_num_heads"]), int(config["mamba_head_dim"]),
               int(config["ssm_state_size"]))
    g, eps = int(config["n_groups"]), config["layer_norm_epsilon"]
    d_in = h * p
    conv_dim = d_in + 2 * g * n
    proj = u @ r(w["in_proj"])
    z, dt = proj[:, :d_in], proj[:, d_in + conv_dim:]
    xbc = proj[:, d_in:d_in + conv_dim]
    del proj
    taps = r(w["conv_w"])
    length = taps.shape[0]
    padded = jnp.concatenate([jnp.zeros((length - 1, conv_dim)), xbc])
    acc = w["conv_b"] + taps[0] * padded[:t]
    for j in range(1, length):
        acc = acc + taps[j] * padded[j:j + t]
    del padded, xbc
    xbc = jax.nn.silu(acc)
    del acc
    x = xbc[:, :d_in].reshape(t, h, p)
    b = xbc[:, d_in:d_in + g * n].reshape(t, g, n)
    c = xbc[:, d_in + g * n:].reshape(t, g, n)
    del xbc
    dt = jax.nn.softplus(dt + w["dt_bias"])                     # (T, H)
    a = -jnp.exp(w["A_log"])
    group = jnp.asarray(np.arange(h) // (h // g))

    def step(state, inputs):
        x_t, b_t, c_t, dt_t = inputs
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[group][:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t[group])

    _, y = jax.lax.scan(step, jnp.zeros((h, p, n)), (x, b, c, dt))
    y = (y + x * w["D"][:, None]).reshape(t, d_in) * jax.nn.silu(z)
    del x, z
    parts = y.reshape(t, g, -1)
    y = (parts * jax.lax.rsqrt(jnp.mean(parts * parts, axis=-1,
                                        keepdims=True) + eps)
         ).reshape(t, d_in) * w["norm"]
    return y @ r(w["out_proj"])


def attention(config, w, u, r):
    """The query blocks run as one ``lax.map``: a block is sliced where it
    starts, so one program serves them all (sliced by Python numbers, each
    block would be a program of its own, compiled once a run)."""
    t = u.shape[0]
    heads, groups, d = (int(config["num_attention_heads"]),
                        int(config["num_key_value_heads"]),
                        int(config["head_dim"]))
    blocks = -(-t // QUERY_BLOCK)
    q = (u @ r(w["q"])).reshape(t, heads, d)
    q = jnp.pad(q, ((0, blocks * QUERY_BLOCK - t), (0, 0), (0, 0)))
    k = (u @ r(w["k"])).reshape(t, groups, d)
    v = (u @ r(w["v"])).reshape(t, groups, d)
    # query head h reads key and value head h // (heads / groups)
    k, v = (jnp.repeat(a, heads // groups, axis=1) for a in (k, v))

    def block(start):
        rows = jax.lax.dynamic_slice_in_dim(q, start, QUERY_BLOCK)
        scores = jnp.einsum("qhd,khd->hqk", rows, k) * d ** -0.5
        causal = (start + jnp.arange(QUERY_BLOCK))[:, None] \
            >= jnp.arange(t)[None, :]
        weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", weights, v)

    out = jax.lax.map(block, jnp.arange(blocks) * QUERY_BLOCK)
    return out.reshape(-1, heads * d)[:t] @ r(w["o"])


def latent_moe(config, blocks, u, r):
    """``blocks`` yields ``(first held expert, the layer's float32 weights
    with that block of experts)``. Sigmoid scores; the top
    ``num_experts_per_tok`` of scores plus the selection bias chosen; the
    chosen scores, renormalised and scaled, as gates; the held experts'
    relu2 units on the latent projection, summed and projected back; the
    shared relu2 unit on ``u``. Returns the block's output and the
    choices."""
    out, chosen = 0.0, None
    for first, w in blocks:
        if chosen is None:
            s = jax.nn.sigmoid(u @ r(w["router"]))
            _, chosen = jax.lax.top_k(s + w["selection_bias"],
                                      int(config["num_experts_per_tok"]))
            gates = jnp.take_along_axis(s, chosen, axis=-1)
            if config["norm_topk_prob"]:
                gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-20)
            gates = gates * config["routed_scaling_factor"]
            latent = u @ r(w["latent_down"])
            routed = jnp.zeros_like(latent)
            up = w["latent_up"]
            out = relu2(u @ r(w["shared_in"])) @ r(w["shared_out"])

        def expert(total, one):
            e, w_in, w_out = one
            gate = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)
            return total + gate[:, None] * (
                relu2(latent @ r(w_in)) @ r(w_out)), None

        # the block's experts one after the other, as one ``lax.scan``
        held = w["experts_in"].shape[0]
        routed, _ = jax.lax.scan(expert, routed, (
            first + jnp.arange(held), w["experts_in"], w["experts_out"]))
    return out + routed @ r(up), chosen


# -- the weights: re-derived, and the timed tree held against them -----------

def architecture(config):
    """The program's ``Arch`` for the configuration: its top-level keys are
    the published ``config.json``'s, with the router's width and the whole
    vocabulary under ``published`` and the chip's share in ``run_keys``."""
    from video_features_tpu.models.nemotron_h import arch_from_config
    published = {**config, **{k: config["published"][k] for k in (
        "n_routed_experts", "vocab_size")}}
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


def expert_blocks(arch, timed, index):
    """Layer ``index``'s float32 weights with ``EXPERT_BLOCK`` held experts
    at a time, each block held against the timed tree's."""
    from video_features_tpu.models.nemotron_h import layer_weights
    for start in range(0, arch.experts_held, EXPERT_BLOCK):
        part = dataclasses.replace(
            arch, first_expert=arch.first_expert + start,
            experts_held=min(EXPERT_BLOCK, arch.experts_held - start))
        stop = start + part.experts_held
        ran = {**timed, **{k: timed[k][start:stop]
                           for k in ("experts_in", "experts_out")}}
        yield part.first_expert, held_against(
            ran, layer_weights(part, SEED, index),
            f"layers[{index}][experts {start}:{stop}]")


def window_features(params, config, check_path, rounded):
    from video_features_tpu.models.nemotron_h import (layer_weights,
                                                      outer_weights)
    arch = architecture(config)
    window = int(config["unit"]["window"])
    eps = config["layer_norm_epsilon"]
    ids = np.fromfile(check_path, dtype="<i4")
    assert 0 <= ids.min() and ids.max() < arch.vocab_held, check_path
    spans = [(s, min(s + window, len(ids)))
             for s in range(0, len(ids), window)]

    outer = held_against({k: params[k] for k in ("embed", "final_norm")},
                         outer_weights(arch, SEED), "outer")
    xs = [rounded(outer["embed"][jnp.asarray(ids[s:e])]) for s, e in spans]
    counts = []
    # layers outside, windows inside: a layer's float32 weights are drawn
    # once (a block of experts at a time) and dropped before the next
    for i, kind in enumerate(arch.layer_kinds):
        timed = params["layers"][i]
        routed = []
        for j, x in enumerate(xs):
            if kind == "moe":
                blocks = expert_blocks(arch, timed, i)
                first = next(blocks)
                u = rms_norm(x, first[1]["pre_norm"], eps)
                out, chosen = latent_moe(config, [first, *blocks], u,
                                         rounded)
                routed.append(np.bincount(
                    np.asarray(chosen).ravel(),
                    minlength=int(config["published"]["n_routed_experts"])))
            else:
                w = held_against(timed, layer_weights(arch, SEED, i),
                                 f"layers[{i}]")
                u = rms_norm(x, w["pre_norm"], eps)
                out = (mamba if kind == "mamba" else attention)(
                    config, w, u, rounded)
                del w
            xs[j] = x + out
        if routed:
            counts.append(routed)
    feats = np.stack([np.asarray(rms_norm(
        x, outer["final_norm"], eps).mean(axis=0)) for x in xs])
    return {config["run_keys"]["feature_type"]: feats.astype(np.float32),
            # (E layers, windows, experts) -> windows first
            "expert_tokens": np.asarray(counts, np.int32).transpose(1, 0, 2)}


def features(params, config, check_path):
    return window_features(params, config, check_path, lambda a: a)


def control(params, config, check_path):
    return window_features(
        params, config, check_path,
        lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32))
