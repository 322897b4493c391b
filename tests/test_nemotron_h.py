"""nemotron_h: the model against its plain reference block by block and over
the stack, the grouped scan against the token-by-token recurrence, packing
(the scan's state, the taps and attention stay inside a segment), what the
comparison notices, the four shares of a LatentMoE layer, and the normal
path.

Tiny widths (hidden 64; Mamba 8 heads of 16 with state 16 in 4 groups of
B/C, chunk 8; attention 4 query heads of 16 over 2; 16 experts top 5 in a
latent of 32 beside a shared unit of 48), the pattern ``MEM*E``, the
published scale and selection rule, a seeded selection bias. Every
tolerance says where it comes from.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_granite_hybrid import (ROW, documents, pack, relative,
                                       token_file)
from video_features_tpu.models import nemotron_h as nem
from video_features_tpu.ops import moe, ssd
from video_features_tpu.reference import nemotron_h as ref

pytestmark = pytest.mark.quick

TINY = dict(
    hidden_size=64, num_hidden_layers=5, hybrid_override_pattern="MEM*E",
    vocab_size=512, layer_norm_epsilon=1e-5, mamba_num_heads=8,
    mamba_head_dim=16, ssm_state_size=16, n_groups=4, conv_kernel=4,
    chunk_size=8, expand=2, time_step_min=0.001, time_step_max=0.1,
    time_step_floor=1e-4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, n_routed_experts=16, num_experts_per_tok=5,
    moe_intermediate_size=32, moe_latent_size=32,
    moe_shared_expert_intermediate_size=48, routed_scaling_factor=5,
    norm_topk_prob=True, mlp_hidden_act="relu2", n_group=1, topk_group=1,
    n_shared_experts=1, use_conv_bias=True)
SEGMENTS = 4      # lines the step returns per row (a row is ROW = 96 tokens)

#: float32 program against the float32 reference: the chunked scan against
#: the recurrence and the grouped products against the loop sum the same
#: terms in another order; measured 3.8e-7 to 4.2e-7 of the largest state
#: over three documents, held to 1e-5 as the other token families are
F32_BAND = 1e-5
#: bfloat16 program (weights rounded once, activations bfloat16; float32
#: state, router, norms and softmax) against the reference on the unrounded
#: weights: measured 6.8e-3 to 8.2e-3 of the largest state over the row's
#: three documents. Three times the largest reading
BF16_BAND = 2.5e-2


@pytest.fixture(scope="module")
def arch():
    return nem.arch_from_config(TINY)


def reference_weights(arch, seed=0):
    layers = [nem.layer_weights(arch, seed, i)
              for i in range(len(arch.layer_kinds))]
    return (lambda i: layers[i]), nem.outer_weights(arch, seed)


@pytest.fixture(scope="module")
def weights(arch):
    return reference_weights(arch)


@pytest.fixture(scope="module")
def step(arch):
    """``step(rows, dtype=float32, state=float32, router=float32)`` -> the
    per-token states, the routers' choices and the step's pooled lines of
    packed rows."""
    cache = {}

    def run(rows, dtype=jnp.float32, state=jnp.float32, router=jnp.float32):
        key = (jnp.dtype(dtype), jnp.dtype(state), jnp.dtype(router),
               rows.shape)
        if key not in cache:
            params = nem.init_params(arch, 0, dtype)

            def fn(p, r):
                f, chosen = nem.token_states(arch, p, r, dtype, state, router)
                return f, chosen, nem.pool_segments(
                    nem.FAMILY, arch.n_routed_experts, SEGMENTS, r[:, 1], f,
                    chosen)

            cache[key] = (params, jax.jit(fn))
        params, fn = cache[key]
        return tuple(np.asarray(x) for x in fn(params, jnp.asarray(rows)))

    return run


def plainly(arch, weights, doc, **changed):
    with jax.default_matmul_precision("highest"):
        return tuple(np.asarray(x) for x in
                     ref.token_states(arch, *weights, doc, **changed))


DOCS = (40, 24, 30)


@pytest.fixture(scope="module")
def truth(arch, weights):
    """Three documents of one packed row (and two positions of padding) and
    the reference's states and choices for each, computed once."""
    docs = documents(1, DOCS)
    return docs, [plainly(arch, weights, doc) for doc in docs]


def loud(w):
    """An E layer's weights with the routed path scaled up: at these widths
    the seeded relu2 experts add ~1e-5 of what the shared unit does (a
    square of small values), so a test of the routed path alone scales them
    (the latent projection by 5, the experts by 10) on both sides."""
    return {**w, "latent_down": 5 * w["latent_down"],
            "experts_in": 10 * w["experts_in"],
            "experts_out": 10 * w["experts_out"]}


def segments_of(f, docs):
    at = 0
    for doc in docs:
        yield f[0, at:at + len(doc)]
        at += len(doc)


def test_the_tiny_architecture_has_every_kind_of_block(arch):
    assert arch.layer_kinds == ("mamba", "moe", "mamba", "attn", "moe")
    assert arch.counter_shape == (2, 16)
    assert (arch.d_inner, arch.conv_dim) == (128, 128 + 2 * 4 * 16)
    params = jax.eval_shape(lambda: nem.init_params(arch, 0, jnp.bfloat16))
    mamba, routed = params["layers"][0], params["layers"][1]
    assert mamba["in_proj"].shape == (64, 128 + 256 + 8)
    assert mamba["conv_w"].shape == (4, 256)
    # the vectors stay float32 beside bfloat16 matrices
    for name in ("conv_b", "dt_bias", "A_log", "D", "norm", "pre_norm"):
        assert mamba[name].dtype == jnp.float32, name
    assert routed["selection_bias"].dtype == jnp.float32
    # non-gated experts in the latent: (E, latent, I) and (E, I, latent)
    assert routed["experts_in"].shape == (16, 32, 32)
    assert routed["experts_out"].shape == (16, 32, 32)
    assert routed["latent_down"].shape == (64, 32)
    assert routed["shared_in"].shape == (64, 48)
    assert params["layers"][3]["k"].shape == (64, 32)
    # the published initialisation: A from 1 to H, dt inside its range
    w = nem.layer_weights(arch, 0, 0)
    np.testing.assert_allclose(np.exp(w["A_log"]), np.arange(1, 9),
                               rtol=1e-6)
    dt = np.asarray(jax.nn.softplus(w["dt_bias"]))
    assert (1e-3 * 0.999 <= dt).all() and (dt <= 0.1 * 1.001).all()


# -- (1) the model against the plain reference ------------------------------------

@pytest.mark.parametrize("kind", ["mamba", "attn", "moe"])
def test_float32_model_is_the_reference_block_by_block(arch, weights, kind):
    """One block of each kind on a document of 37 tokens (the scan's chunk
    of 8 divides none of it), the model's function against the
    reference's."""
    index = arch.layer_kinds.index(kind)
    w = weights[0](index)
    if kind == "moe":
        w = loud(w)
    u = np.random.default_rng(2).standard_normal((37, 64)).astype(np.float32)
    seg = jnp.ones((1, 37), jnp.int32)
    with jax.default_matmul_precision("highest"):
        if kind == "mamba":
            got = nem.mamba_mixer(arch, w, jnp.asarray(u[None]), seg)[0]
            want = ref.mamba(arch, w, u)
        elif kind == "attn":
            got = nem.attention_mixer(arch, w, jnp.asarray(u[None]), seg)[0]
            want = ref.attention(arch, w, u)
        else:
            got, chosen = nem.latent_moe(arch, w, jnp.asarray(u),
                                         jnp.ones((37,), bool))
            got = got + moe.gated_unit(jnp.asarray(u), w["shared_in"],
                                       w["shared_out"], activation="relu2")
            want, want_chosen = ref.routed(arch, w, u)
            want = want + ref.shared(w, u)
            assert np.array_equal(np.sort(np.asarray(chosen), -1),
                                  np.sort(np.asarray(want_chosen), -1))
    assert relative(np.asarray(got), np.asarray(want)) < F32_BAND


def test_float32_model_is_the_reference_over_the_stack(step, truth):
    docs, wanted = truth
    f, chosen, _ = step(pack(docs))
    assert chosen.shape == (2, 1, ROW, 5)       # the E layers route
    for got, (want, _) in zip(segments_of(f, docs), wanted):
        assert relative(got, want) < F32_BAND
    at = 0
    for doc, (_, want_chosen) in zip(docs, wanted):
        assert np.array_equal(np.sort(chosen[:, 0, at:at + len(doc)], -1),
                              np.sort(want_chosen, -1))
        at += len(doc)


def test_bfloat16_model_is_inside_its_band(step, truth):
    docs, wanted = truth
    f, _, _ = step(pack(docs), jnp.bfloat16)
    worst = max(relative(got, want)
                for got, (want, _) in zip(segments_of(f, docs), wanted))
    assert F32_BAND < worst < BF16_BAND


def test_a_bfloat16_state_fails_where_float32_passes(step, truth):
    """The float32 model with only its carried scan state in bfloat16
    leaves the float32 band that the model passes (measured on the row's
    three documents: 3.8e-7 to 4.2e-7 exact, 2.2e-5 to 3.2e-5 with the
    state in bfloat16). Beside bfloat16 activations it is inside
    the activations' own noise at this size: the scan's test below
    separates them there."""
    rows = pack(truth[0])
    docs, wanted = truth

    def worst(f):
        return max(relative(got, want)
                   for got, (want, _) in zip(segments_of(f, docs), wanted))

    assert worst(step(rows)[0]) < F32_BAND \
        < worst(step(rows, state=jnp.bfloat16)[0])


def test_a_bfloat16_router_fails_where_float32_passes(arch, weights):
    """A LatentMoE block (its routed path made loud) with its router's
    logits rounded to bfloat16 leaves the float32 band that the float32
    logits keep (measured 1.3e-7 and 2.4e-4 of the largest output): the
    gates move; over 512 experts, top 22, it swaps experts too
    (``tests/test_moe.py``)."""
    w = loud(weights[0](1))
    u = np.random.default_rng(6).standard_normal((37, 64)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.routed(arch, w, u)

        def error(router_dtype):
            got, _ = nem.latent_moe(arch, w, jnp.asarray(u),
                                    jnp.ones((37,), bool), router_dtype)
            return relative(np.asarray(got), np.asarray(want))

        assert error(jnp.float32) < F32_BAND < error(jnp.bfloat16)


# -- (2) the grouped scan against the token-by-token recurrence ------------------

def recurrence(x, dt, a, b, c, seg):
    """float64, token by token, the state zeroed at each segment's start;
    ``b`` / ``c`` (T, G, N), head ``h`` reading group ``h // (H / G)``."""
    t, h, p = x.shape
    group = np.arange(h) // (h // b.shape[1])
    y = np.zeros((t, h, p))
    state = np.zeros((h, p, b.shape[-1]))
    for i in range(t):
        if i == 0 or seg[i] != seg[i - 1]:
            state[:] = 0.0
        state = np.exp(dt[i] * a)[:, None, None] * state \
            + (dt[i][:, None] * x[i])[:, :, None] * b[i][group][:, None, :]
        y[i] = np.einsum("hpn,hn->hp", state, c[i][group])
    return y


@pytest.mark.parametrize("chunk", [4, 8, 13])
@pytest.mark.parametrize("groups", [1, 2, 4])
def test_the_grouped_scan_is_the_recurrence(groups, chunk):
    """29 tokens in three segments and a padding tail, 8 heads over
    ``groups`` groups of B/C: float32 against float64, 1e-5 of the largest
    output, whatever the chunk."""
    rng = np.random.default_rng(groups)
    t, h, p, n = 29, 8, 8, 16
    x = rng.standard_normal((t, h, p))
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (t, h)))
    a = -rng.uniform(1.0, 16.0, h)
    b, c = (rng.standard_normal((t, groups, n)) for _ in range(2))
    seg = np.array([1] * 8 + [2] * 8 + [3] * 9 + [0] * 4)
    want = recurrence(x, dt, a, b, c, seg)

    def scan(b_in, c_in):
        return np.asarray(ssd.ssd_scan(
            *(jnp.asarray(v[None], jnp.float32) for v in (x, dt)),
            jnp.asarray(a, jnp.float32),
            *(jnp.asarray(v[None], jnp.float32) for v in (b_in, c_in)),
            jnp.asarray(seg[None]), chunk)[0])

    assert relative(scan(b, c), want) < 1e-5
    if groups == 1:
        # one group given without its axis is the same scan, its sums
        # in another order
        assert relative(scan(b[:, 0], c[:, 0]), scan(b, c)) < 1e-6


def test_two_rows_of_the_grouped_scan_are_each_their_recurrence():
    """Two rows of 45 tokens in chunks of 8 (no multiple: three padding
    tokens of a segment of their own), 8 heads over 2 groups: the first
    row's boundaries at 16 and 40 lie on chunk edges, the second's at 13
    and 24 inside and on one, with a padding tail. Each row is its own
    token-by-token recurrence: float32 against float64, 1e-5 of the largest
    output; nothing of one row reaches the other."""
    rng = np.random.default_rng(7)
    rows, t, h, p, n, g = 2, 45, 8, 8, 16, 2
    x = rng.standard_normal((rows, t, h, p))
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (rows, t, h)))
    a = -rng.uniform(1.0, 16.0, h)
    b, c = (rng.standard_normal((rows, t, g, n)) for _ in range(2))
    seg = np.array([[1] * 16 + [2] * 24 + [3] * 5,
                    [1] * 13 + [2] * 11 + [3] * 17 + [0] * 4])
    got = np.asarray(ssd.ssd_scan(
        *(jnp.asarray(v, jnp.float32) for v in (x, dt, a, b, c)),
        jnp.asarray(seg), 8))
    for row in range(rows):
        want = recurrence(x[row], dt[row], a, b[row], c[row], seg[row])
        assert relative(got[row], want) < 1e-5


def test_a_bfloat16_grouped_scan_state_fails_where_float32_passes():
    """512 tokens of one document in chunks of 8 over 4 groups, heads that
    forget slowly: the state is the sum of hundreds of tokens and is handed
    on 63 times. Inputs in bfloat16 both times; rounding the carried state
    at every chunk adds its own 2**-9 each time."""
    rng = np.random.default_rng(0)
    t, h, p, n, g = 512, 8, 8, 16, 4
    x = rng.standard_normal((t, h, p))
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(3e-3), (t, h)))
    a = -rng.uniform(1.0, 2.0, h)
    b, c = (rng.standard_normal((t, g, n)) for _ in range(2))
    seg = np.ones(t, np.int32)
    rounded = [np.asarray(jnp.asarray(v, jnp.bfloat16).astype(jnp.float32),
                          np.float64) for v in (x, b, c)]
    want = recurrence(rounded[0], dt, a, rounded[1], rounded[2], seg)

    def error(state_dtype):
        got = ssd.ssd_scan(
            jnp.asarray(x[None], jnp.bfloat16),
            jnp.asarray(dt[None], jnp.float32), jnp.asarray(a, jnp.float32),
            jnp.asarray(b[None], jnp.bfloat16),
            jnp.asarray(c[None], jnp.bfloat16), jnp.asarray(seg[None]), 8,
            state_dtype)
        return float(np.linalg.norm(np.asarray(got[0]) - want)
                     / np.linalg.norm(want))

    assert error(jnp.float32) < 4e-3 < error(jnp.bfloat16)


def test_the_group_norm_takes_each_group_alone():
    """``y * silu(z)`` RMS-normed per group of 32 channels: scaling one
    group's inputs leaves the others' outputs as they were."""
    rng = np.random.default_rng(4)
    y, z = (rng.standard_normal((3, 128)).astype(np.float32)
            for _ in range(2))
    weight = np.ones(128, np.float32)
    base = np.asarray(nem.gated_group_norm(y, z, weight, 4, 1e-5)
                      ).reshape(3, 128)
    scaled = y.copy()
    scaled[:, :32] *= 10.0
    moved = np.asarray(nem.gated_group_norm(scaled, z, weight, 4, 1e-5)
                       ).reshape(3, 128)
    np.testing.assert_allclose(moved[:, 32:], base[:, 32:], rtol=1e-6)
    gated = (y * z / (1 + np.exp(-z))).reshape(3, 4, 32)
    want = gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(base, want.reshape(3, 128), rtol=2e-5,
                               atol=1e-6)


# -- (3) packing: the scan's state, the taps and the mask ------------------------

@pytest.mark.parametrize("where", ["first", "middle", "last"])
def test_a_packed_document_reads_what_it_reads_alone(arch, step, truth,
                                                     where):
    docs, _ = truth
    i = {"first": 0, "middle": 1, "last": 2}[where]
    packed, _, lines = step(pack(docs))
    alone, _, alone_lines = step(pack([docs[i]]))
    got = list(segments_of(packed, docs))[i]
    assert relative(got, alone[0, :len(docs[i])]) < F32_BAND
    hidden = arch.feature_dim
    assert relative(lines[0, i, :hidden], alone_lines[0, 0, :hidden]) \
        < F32_BAND
    # the counts behind the feature are whole numbers and the same
    assert np.array_equal(lines[0, i, hidden:], alone_lines[0, 0, hidden:])
    assert lines[0, i, hidden:].sum() == 2 * 5 * len(docs[i])


def test_a_mamba_block_reads_nothing_across_a_segment(arch, weights):
    """A Mamba block on a row with a boundary after token 7 (inside the
    second chunk of 8 would be token 8 on): the second segment's outputs
    do not move when the first segment's inputs do, and they do where the
    row is one segment (through the taps and the carried state)."""
    w = weights[0](0)
    rng = np.random.default_rng(5)
    u = rng.standard_normal((1, 20, 64)).astype(np.float32)
    other = u.copy()
    other[0, :7] = rng.standard_normal((7, 64))
    split = np.array([[1] * 7 + [2] * 13])

    def second(x, seg):
        with jax.default_matmul_precision("highest"):
            out = nem.mamba_mixer(arch, w, jnp.asarray(x), jnp.asarray(seg))
        return np.asarray(out)[0, 7:]

    assert np.array_equal(second(u, split), second(other, split))
    one = np.ones_like(split)
    assert np.abs(second(u, one) - second(other, one)).max() > 1e-4


# -- (4) the comparison notices -------------------------------------------------------

#: what each change does to the reference, measured against the float32
#: program on the row's second document: 0.27 with one group for the norm,
#: 8.9e-3 with the groups of B/C interleaved over the heads, 7.2e-2 with
#: relu in place of relu2: over a hundred times the band that holds the two
#: together. The selection bias moves the routed path alone, which the
#: seeded experts keep near silent at these widths: the test after this one
CHANGED = {
    "one group for the norm": dict(norm_groups=1),
    "B/C groups interleaved over the heads": dict(bc_groups="interleaved"),
    "relu in place of relu2": dict(activation=jax.nn.relu),
}


@pytest.mark.parametrize("what", list(CHANGED))
def test_the_comparison_notices(arch, weights, step, truth, what):
    docs, wanted = truth
    wrong, _ = plainly(arch, weights, docs[1], **CHANGED[what])
    f, _, _ = step(pack(docs))
    got = list(segments_of(f, docs))[1]
    assert relative(got, wanted[1][0]) < F32_BAND
    assert relative(got, wrong) > 100 * F32_BAND, what


def test_the_comparison_notices_the_selection_bias(arch, weights):
    """A LatentMoE block with its routed path made loud: the reference
    without the bias in the selection chooses other experts and reads far
    outside the band that holds the program to it."""
    w = loud(weights[0](1))
    u = np.random.default_rng(7).standard_normal((37, 64)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        got, chosen = nem.latent_moe(arch, w, jnp.asarray(u),
                                     jnp.ones((37,), bool))
        want, want_chosen = ref.routed(arch, w, u)
        wrong, wrong_chosen = ref.routed(arch, w, u, selection_bias=False)
    assert relative(np.asarray(got), np.asarray(want)) < F32_BAND
    assert relative(np.asarray(got), np.asarray(wrong)) > 100 * F32_BAND
    assert not np.array_equal(np.sort(np.asarray(wrong_chosen), -1),
                              np.sort(np.asarray(chosen), -1))


def test_what_the_model_cannot_run_is_refused():
    for changed in (dict(mamba_proj_bias=True), dict(use_conv_bias=False),
                    dict(mlp_hidden_act="silu"), dict(n_group=8),
                    dict(moe_latent_size=None),
                    dict(hybrid_override_pattern="M-M*E"),
                    dict(hybrid_override_pattern="MEM"),
                    dict(num_hidden_layers=1),
                    dict(mamba_num_heads=4)):
        with pytest.raises(NotImplementedError, match="nemotron_h"):
            nem.arch_from_config(dict(TINY, **changed))
    with pytest.raises(ValueError, match="layer_shards"):
        nem.arch_from_config(TINY, layer_shards=3)
    # the cut in depth cuts the pattern with it
    cut = nem.arch_from_config(dict(TINY, num_hidden_layers=2))
    assert cut.hybrid_override_pattern == "ME"


# -- (5) the chip's share ---------------------------------------------------------------

def test_the_four_expert_shares_add_up_to_the_uncut_layer(arch):
    """With ``layer_shards`` 4 each chip's routed part is the reference's
    share, and the four shares, with the shared unit and the two latent
    projections counted once, are the uncut layer: the router, the bias,
    the projections and expert ``e`` are the same whichever share holds
    it."""
    u = jnp.asarray(np.random.default_rng(3).normal(size=(33, 64)),
                    jnp.float32)
    whole = loud(nem.layer_weights(arch, 0, 1))
    with jax.default_matmul_precision("highest"):
        want, want_chosen = ref.routed(arch, whole, u)
        want = want + ref.shared(whole, u)
        total = ref.shared(whole, u)
        for rank in range(4):
            part = nem.arch_from_config(TINY, layer_shards=4,
                                        layer_shard_rank=rank)
            assert (part.first_expert, part.experts_held) == (4 * rank, 4)
            w = loud(nem.layer_weights(part, 0, 1))
            assert np.array_equal(w["experts_in"],
                                  whole["experts_in"][4 * rank:4 * rank + 4])
            for name in ("router", "selection_bias", "latent_down",
                         "latent_up", "shared_in"):
                assert np.array_equal(w[name], whole[name]), name
            out, chosen = ref.routed(part, w, u)
            assert np.array_equal(chosen, want_chosen)
            got, picks = nem.latent_moe(part, w, u, jnp.ones((33,), bool))
            assert np.array_equal(np.asarray(picks), np.asarray(chosen))
            assert relative(np.asarray(got), np.asarray(out)) < F32_BAND
            total = total + got
    assert relative(np.asarray(total), np.asarray(want)) < F32_BAND


# -- the normal path ------------------------------------------------------------------------

def tiny_keys(tmp, **more):
    keys = dict(
        feature_type="nemotron_h", architecture=dict(TINY), device="cpu",
        allow_random_weights=True, stack_size=ROW, batch_size=2,
        max_segments=SEGMENTS, layer_shards=2, on_extraction="save_numpy",
        output_path=str(tmp / "out"), tmp_path=str(tmp / "tmp"))
    keys.update(more)
    return keys


@pytest.fixture(scope="module")
def extractor(tmp_path_factory):
    from video_features_tpu.config import load_config, sanity_check
    from video_features_tpu.registry import get_extractor_cls
    args = load_config("nemotron_h",
                       tiny_keys(tmp_path_factory.mktemp("nem")))
    sanity_check(args, require_videos=False)
    return get_extractor_cls("nemotron_h")(args)


def test_the_extractor_agrees_with_the_reference_window_by_window(
        extractor, tmp_path):
    """Half the experts and half the vocabulary held (``layer_shards`` 2):
    the reference takes the same share."""
    arch = extractor.arch
    assert (arch.experts_held, arch.vocab_held) == (8, 256)
    (doc,) = documents(4, (230,))
    got = extractor.extract(token_file(tmp_path / "long.tokens", doc))
    assert got["nemotron_h"].shape == (3, 64)            # ceil(230 / 96)
    assert got["nemotron_h"].dtype == np.float32
    assert got["expert_tokens"].shape == (3, 2, 16)
    with jax.default_matmul_precision("highest"):
        feats, counts = ref.features(arch, *reference_weights(arch), doc,
                                     ROW, ROW)
    assert relative(got["nemotron_h"], feats) < F32_BAND
    assert np.array_equal(got["expert_tokens"], counts)
    assert got["expert_tokens"].sum(axis=(1, 2)).tolist() == [
        2 * 5 * 96, 2 * 5 * 96, 2 * 5 * 38]
    with pytest.raises(ValueError, match="vocabulary rows held"):
        extractor.extract(token_file(tmp_path / "bad.tokens", [3, 256]))


def test_the_first_item_states_the_experts_and_every_item_its_held_share(
        extractor, tmp_path):
    """One ``moe`` event on the first item's span, with the router's rule
    and the experts' form beside how the grouped products run;
    ``moe.held_share`` and ``moe.fullest_over_mean`` on the program's own
    timeline, one sample a routed layer, from the counts the step
    returned."""
    from video_features_tpu.telemetry import trace
    from video_features_tpu.telemetry.spans import VideoSpan
    from video_features_tpu.utils.profiling import profiler
    (doc,) = documents(8, (50,), vocab=256)
    path = token_file(tmp_path / "doc.tokens", doc)
    extractor._moe_stated = False
    profiler.set_trace_hook(lambda name, t0, dt: None)  # records in memory
    try:
        with VideoSpan(path) as span:
            got = extractor.extract(path)
            extractor.extract(path)
    finally:
        profiler.set_trace_hook(None)
    (stated,) = [e for e in span.record["events"] if e["kind"] == "moe"]
    assert {k: stated[k] for k in (
        "scoring", "selection_bias", "top_k", "activation", "latent",
        "scaling", "experts", "products", "widths")} == {
        "scoring": "sigmoid", "selection_bias": True, "top_k": 5,
        "activation": "relu2", "latent": 32, "scaling": 5.0,
        "experts": "8 of 16", "products": "ragged_dot",
        "widths": [[32, 32], [32, 32]]}
    events = [e for e in trace.last_recording().events()
              if e.get("ph") == "C" and e["name"] == "moe.held_share"]
    a_layer = got["expert_tokens"].sum(axis=0)           # (2 layers, 16)
    want = [{f"layer{i}": float(row[:8].sum() / row.sum())}
            for i, row in enumerate(a_layer)]
    assert [e["args"] for e in events[:2]] == want
    assert all(0.0 <= v <= 1.0 for e in want for v in e.values())
