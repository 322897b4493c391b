"""lfm2_moe: the model against its plain reference, packing (the taps and the
attention stay inside a segment, positions restart), what the comparison
notices, the chip's share of the experts, and the normal path.

Tiny widths (hidden 64, 4 query heads of 16 over 2 key/value heads, 8
experts top-3 of width 32), one leading dense layer and three routed ones
(``conv``, ``full_attention``, ``conv``, ``full_attention``), the published
rotary theta and a seeded ``expert_bias``. Every tolerance says where it
comes from.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_granite_hybrid import (ROW, documents, pack, relative,
                                       token_file)
from video_features_tpu.models import lfm2_moe as lfm
from video_features_tpu.ops import ssd
from video_features_tpu.reference import lfm2_moe as ref

pytestmark = pytest.mark.quick

TINY = dict(
    hidden_size=64, num_hidden_layers=4, vocab_size=512, norm_eps=1e-5,
    layer_types=["conv", "full_attention", "conv", "full_attention"],
    conv_L_cache=3, conv_bias=False, num_attention_heads=4,
    num_key_value_heads=2, rope_theta=1000000, intermediate_size=96,
    moe_intermediate_size=32, num_dense_layers=1, num_experts=8,
    num_experts_per_tok=3, norm_topk_prob=True, routed_scaling_factor=1,
    use_expert_bias=True)
SEGMENTS = 4      # lines the step returns per row (a row is ROW = 96 tokens)

#: float32 program against the float32 reference: both sum the same few
#: hundred terms in another order; measured 3.0e-7 to 3.3e-7 of the largest
#: state over three rows, held to 1e-5 as the other token families are
F32_BAND = 1e-5
#: bfloat16 program (weights rounded once, activations bfloat16, float32
#: router, angles, norms and softmax) against the reference on the unrounded
#: weights: measured 8.0e-3, 2.3e-2 and 3.3e-2 of the largest state over
#: three rows (the larger two where rounding hands a token's last choice to
#: another expert). Three times the largest reading
BF16_BAND = 0.1


@pytest.fixture(scope="module")
def arch():
    return lfm.arch_from_config(TINY)


def reference_weights(arch, seed=0):
    layers = [lfm.layer_weights(arch, seed, i)
              for i in range(len(arch.layer_types))]
    return (lambda i: layers[i]), lfm.outer_weights(arch, seed)


@pytest.fixture(scope="module")
def weights(arch):
    return reference_weights(arch)


@pytest.fixture(scope="module")
def step(arch):
    """``step(rows, dtype=float32, router_dtype=float32)`` -> the per-token
    states, the routers' choices and the step's pooled lines of packed
    rows."""
    cache = {}

    def run(rows, dtype=jnp.float32, router_dtype=jnp.float32):
        key = (jnp.dtype(dtype), jnp.dtype(router_dtype), rows.shape)
        if key not in cache:
            params = lfm.init_params(arch, 0, dtype)

            def fn(p, r):
                f, chosen = lfm.token_states(arch, p, r, dtype, router_dtype)
                return f, chosen, lfm.pool_segments(
                    lfm.FAMILY, arch.num_experts, SEGMENTS, r[:, 1], f,
                    chosen)

            cache[key] = (params, jax.jit(fn))
        params, fn = cache[key]
        return tuple(np.asarray(x) for x in fn(params, jnp.asarray(rows)))

    return run


def plainly(arch, weights, doc, **changed):
    with jax.default_matmul_precision("highest"):
        return tuple(np.asarray(x) for x in
                     ref.token_states(arch, *weights, doc, **changed))


# -- (1) the model against the plain reference ------------------------------------

DOCS = (40, 24, 30)


@pytest.fixture(scope="module")
def truth(arch, weights):
    """Three documents of one packed row (and two positions of padding) and
    the reference's states and choices for each, computed once."""
    docs = documents(1, DOCS)
    return docs, [plainly(arch, weights, doc) for doc in docs]


def segments_of(f, docs):
    at = 0
    for doc in docs:
        yield f[0, at:at + len(doc)]
        at += len(doc)


def test_the_tiny_architecture_has_every_kind_of_layer(arch):
    assert arch.layer_kinds == ("conv/dense", "attn/moe", "conv/moe",
                                "attn/moe")
    assert (arch.head_dim, arch.counter_shape) == (16, (3, 8))
    params = jax.eval_shape(lambda: lfm.init_params(arch, 0, jnp.bfloat16))
    routed = params["layers"][1]
    # the bias and the norms stay float32 beside bfloat16 matrices
    assert routed["expert_bias"].dtype == jnp.float32
    assert routed["op"]["q_norm"].shape == (16,)
    assert routed["experts_in"].shape == (8, 64, 64)
    assert params["layers"][0]["op"]["conv_w"].shape == (3, 64)
    assert params["layers"][0]["op"]["in_proj"].shape == (64, 192)


def test_float32_model_is_the_reference_token_by_token(step, truth):
    docs, wanted = truth
    f, chosen, _ = step(pack(docs))
    assert chosen.shape == (3, 1, ROW, 3)       # the dense layer routes none
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


# -- (2) packing: the taps, the mask and the restarted positions ----------------------

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
    assert lines[0, i, hidden:].sum() == 3 * 3 * len(docs[i])


def test_the_taps_read_nothing_across_a_segment(arch, weights):
    """The short convolution of a row with a boundary after token 7: the
    second segment's outputs do not move when the first segment's inputs
    do, and they do where the row is one segment."""
    w = weights[0](0)["op"]
    rng = np.random.default_rng(5)
    u = rng.standard_normal((1, 20, 64)).astype(np.float32)
    other = u.copy()
    other[0, :7] = rng.standard_normal((7, 64))
    split = np.array([[1] * 7 + [2] * 13])

    def second(x, seg):
        with jax.default_matmul_precision("highest"):
            out = lfm.short_conv(arch, w, jnp.asarray(x), jnp.asarray(seg))
        return np.asarray(out)[0, 7:]

    assert np.array_equal(second(u, split), second(other, split))
    one = np.ones_like(split)
    moved = np.abs(second(u, one) - second(other, one)).max(axis=-1)
    # the two taps behind the boundary read the first segment (outputs near
    # 1e-3 at these widths), the rest of the row reads nothing of it
    assert (moved[:2] > 1e-4).all() and (moved[2:] == 0).all()
    # and the taps alone, with no bias, against the plain sum
    x = rng.standard_normal((1, 12, 5)).astype(np.float32)
    taps = rng.standard_normal((3, 5)).astype(np.float32)
    seg = np.array([[1] * 5 + [2] * 7])
    got = np.asarray(ssd.causal_conv1d(jnp.asarray(x), jnp.asarray(taps),
                                       None, jnp.asarray(seg)))
    for start, end in ((0, 5), (5, 12)):
        alone = np.concatenate([np.zeros((2, 5), np.float32), x[0, start:end]])
        want = sum(alone[j:j + end - start] * taps[j] for j in range(3))
        assert np.allclose(got[0, start:end], want, atol=1e-6)


def test_the_rotary_tables_turn_the_whole_head_from_each_segments_start(arch):
    seg = jnp.asarray([[1, 1, 1, 2, 2, 0]], jnp.int32)
    cos, sin = lfm.rotary_tables(arch, seg)
    assert cos.shape == (1, 6, 8)
    inv_freq = 1e6 ** (-np.arange(0, 16, 2) / 16)
    for t, p in enumerate([0, 1, 2, 0, 1]):
        np.testing.assert_allclose(np.asarray(cos[0, t]), np.cos(p * inv_freq),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(sin[0, t]), np.sin(p * inv_freq),
                                   rtol=1e-6, atol=1e-7)
    # channel j turns with channel j + 8, not with j + 1
    x = np.zeros((1, 1, 16), np.float32)
    x[..., 0] = 1.0
    turned = np.asarray(lfm.rotate(jnp.asarray(x), jnp.asarray(cos[:, 1:2]),
                                   jnp.asarray(sin[:, 1:2])))
    assert turned[0, 0, 0] == pytest.approx(np.cos(1.0))
    assert turned[0, 0, 8] == pytest.approx(np.sin(1.0))
    assert turned[0, 0, 1] == 0.0


# -- (3) the comparison notices -------------------------------------------------------

#: what each change does to the reference, measured against the float32
#: program on the row's second document: 5.8e-2 without the bias in the
#: selection, 3.0e-3 with it in the gates, 5.0e-2 with a softmax, 0.49 with
#: interleaved pairs (the per-head norms make the scores sharp): each is
#: over a hundred times the band that holds the two together
CHANGED = {
    "the bias dropped from the selection": dict(selection_bias=False),
    "the bias put into the gates": dict(bias_in_gates=True),
    "softmax in place of the sigmoid": dict(scoring="softmax"),
    "interleaved rotary": dict(rotary_layout="interleaved"),
}


@pytest.mark.parametrize("what", list(CHANGED))
def test_the_comparison_notices(arch, weights, step, truth, what):
    docs, wanted = truth
    wrong, _ = plainly(arch, weights, docs[1], **CHANGED[what])
    f, _, _ = step(pack(docs))
    got = list(segments_of(f, docs))[1]
    assert relative(got, wanted[1][0]) < F32_BAND
    assert relative(got, wrong) > 100 * F32_BAND, what


def test_the_seeded_bias_moves_choices(arch, weights, truth):
    """Without the bias in the selection the reference chooses other
    experts for some tokens: the counts beside a feature see a rule that
    leaves it out."""
    docs, wanted = truth
    moved = 0
    for doc, (_, want) in zip(docs, wanted):
        _, unbiased = plainly(arch, weights, doc, selection_bias=False)
        moved += int((np.sort(unbiased, -1) != np.sort(want, -1))
                     .any(-1).sum())
    assert moved > 0


def test_a_bfloat16_router_swaps_experts_where_the_float32_router_does_not():
    """The sigmoid rule over 4,096 tokens of width 1,024, 32 experts, top 4,
    with a bias of the seeded scale; inputs and weights exact in bfloat16:
    float32 logits choose what float64 chooses; logits rounded to bfloat16
    swap the fourth and fifth expert for 0.7% of the tokens."""
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.standard_normal((4096, 1024)), jnp.bfloat16)
    w = jnp.asarray(0.02 * rng.standard_normal((1024, 32)), jnp.bfloat16)
    bias = (lfm.EXPERT_BIAS_STD * rng.standard_normal(32)).astype(np.float32)
    exact = np.asarray(u, np.float64) @ np.asarray(w, np.float64)
    biased = 1.0 / (1.0 + np.exp(-exact)) + bias
    want = np.sort(np.argsort(-biased, axis=1)[:, :4], axis=1)

    def swapped(router_dtype):
        _, chosen = lfm.moe.route(u, w, 4, router_dtype, rule="sigmoid",
                                  selection_bias=bias)
        return float((np.sort(np.asarray(chosen), 1) != want).any(1).mean())

    assert swapped(jnp.float32) < 0.001 < 0.004 < swapped(jnp.bfloat16)


def test_what_the_model_cannot_run_is_refused():
    for changed in (dict(conv_bias=True),
                    dict(use_expert_bias=False),
                    dict(layer_types=["conv", "sliding_attention", "conv",
                                      "conv"]),
                    dict(num_hidden_layers=1)):
        with pytest.raises(NotImplementedError, match="lfm2_moe"):
            lfm.arch_from_config(dict(TINY, **changed))
    with pytest.raises(ValueError, match="layer_shards"):
        lfm.arch_from_config(TINY, layer_shards=3)
    # the cut in depth cuts layer_types with it
    cut = lfm.arch_from_config(dict(TINY, num_hidden_layers=2))
    assert cut.layer_types == ("conv", "full_attention")


# -- (4) the chip's share ---------------------------------------------------------------

def test_the_two_expert_shares_add_up_to_the_uncut_layer(arch):
    """With ``layer_shards`` 2 the routed parts of the two shares are the
    uncut reference's layer, and each is the program's share."""
    u = jnp.asarray(np.random.default_rng(3).normal(size=(33, 64)),
                    jnp.float32)
    whole = lfm.layer_weights(arch, 0, 1)
    with jax.default_matmul_precision("highest"):
        want, want_chosen = ref.routed(arch, whole, u)
        total = jnp.zeros_like(want)
        for rank in (0, 1):
            part = lfm.arch_from_config(TINY, layer_shards=2,
                                        layer_shard_rank=rank)
            assert (part.first_expert, part.experts_held) == (4 * rank, 4)
            w = lfm.layer_weights(part, 0, 1)
            # expert e, the router and the bias are the same whichever
            # share holds it
            assert np.array_equal(w["experts_in"],
                                  whole["experts_in"][4 * rank:4 * rank + 4])
            assert np.array_equal(w["expert_bias"], whole["expert_bias"])
            out, chosen = ref.routed(part, w, u)
            assert np.array_equal(chosen, want_chosen)
            total = total + out
            gates, picks = lfm.moe.route(
                u, w["router"], 3, rule="sigmoid",
                selection_bias=w["expert_bias"])
            routed = lfm.moe.held_experts(
                u, gates, picks, w["experts_in"], w["experts_out"],
                part.first_expert, jnp.ones((33,), bool), 8)
            assert relative(np.asarray(routed), np.asarray(out)) < F32_BAND
    assert relative(np.asarray(total), np.asarray(want)) < F32_BAND


# -- the normal path ------------------------------------------------------------------------

def tiny_keys(tmp, **more):
    keys = dict(
        feature_type="lfm2_moe", architecture=dict(TINY), device="cpu",
        allow_random_weights=True, stack_size=ROW, batch_size=2,
        max_segments=SEGMENTS, on_extraction="save_numpy",
        output_path=str(tmp / "out"), tmp_path=str(tmp / "tmp"))
    keys.update(more)
    return keys


@pytest.fixture(scope="module")
def extractor(tmp_path_factory):
    from video_features_tpu.config import load_config, sanity_check
    from video_features_tpu.registry import get_extractor_cls
    args = load_config("lfm2_moe", tiny_keys(tmp_path_factory.mktemp("lfm")))
    sanity_check(args, require_videos=False)
    return get_extractor_cls("lfm2_moe")(args)


def test_the_extractor_agrees_with_the_reference_window_by_window(
        extractor, weights, tmp_path):
    (doc,) = documents(4, (230,))
    got = extractor.extract(token_file(tmp_path / "long.tokens", doc))
    assert got["lfm2_moe"].shape == (3, 64)             # ceil(230 / 96)
    assert got["lfm2_moe"].dtype == np.float32
    assert got["expert_tokens"].shape == (3, 3, 8)
    with jax.default_matmul_precision("highest"):
        feats, counts = ref.features(extractor.arch, *weights, doc, ROW, ROW)
    assert relative(got["lfm2_moe"], feats) < F32_BAND
    assert np.array_equal(got["expert_tokens"], counts)
    assert got["expert_tokens"].sum(axis=(1, 2)).tolist() == [
        3 * 3 * 96, 3 * 3 * 96, 3 * 3 * 38]
    with pytest.raises(ValueError, match="vocabulary rows held"):
        extractor.extract(token_file(tmp_path / "bad.tokens", [3, 512]))


def test_the_first_item_states_the_router_and_every_item_its_fullest_expert(
        extractor, tmp_path):
    """One ``moe`` event on the first item's span, with the router's rule
    beside how the grouped products run; ``moe.fullest_over_mean`` on the
    program's own timeline, one sample a routed layer, from the counts the
    step returned."""
    from video_features_tpu.telemetry import trace
    from video_features_tpu.telemetry.spans import VideoSpan
    from video_features_tpu.utils.profiling import profiler
    (doc,) = documents(8, (50,))
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
    assert {k: stated[k] for k in ("scoring", "selection_bias", "top_k",
                                   "products", "rows", "experts",
                                   "widths")} == {
        "scoring": "sigmoid", "selection_bias": True, "top_k": 3,
        "products": "ragged_dot", "rows": 2 * ROW * 3, "experts": 8,
        "widths": [[64, 64], [32, 64]]}
    events = [e for e in trace.last_recording().events()
              if e.get("ph") == "C" and e["name"] == "moe.fullest_over_mean"]
    a_layer = got["expert_tokens"].sum(axis=0)           # (3 layers, 8)
    want = [{f"layer{i}": float(row.max() / row.mean())}
            for i, row in enumerate(a_layer)]
    assert [e["args"] for e in events[:3]] == want
    assert all(1.0 <= v for e in want for v in e.values())


def test_the_first_item_states_the_tile_attention_scores_a_step_at(
        extractor, tmp_path):
    """One ``attention`` event on the first item's span: a row of ``ROW``
    tokens is one query tile and one key block, over every head."""
    from video_features_tpu.telemetry.spans import VideoSpan
    path = token_file(tmp_path / "doc.tokens", documents(9, (40,))[0])
    extractor._moe_stated = False
    with VideoSpan(path) as span:
        extractor.extract(path)
        extractor.extract(path)
    (tile,) = [e for e in span.record["events"] if e["kind"] == "attention"]
    heads = extractor.arch.num_attention_heads
    assert {k: tile[k] for k in ("q_tile", "block_size", "heads",
                                 "score_tile_mb")} == {
        "q_tile": ROW, "block_size": ROW, "heads": heads,
        "score_tile_mb": round(heads * ROW * ROW * 4 / 1e6, 2)}
