"""deepseek_v2: the model against its plain reference, packing with restarted
positions, YaRN's numbers, what the comparison notices, the chip's share of
the experts, and the normal path.

Tiny widths (hidden 64, 4 heads of 16 + 8 rotary / 16 value, latent 32, 8
experts top-3 of width 32, one dense and two expert layers), YaRN over an
original length of 16 so that the tests' positions pass it, seeded weights.
Every tolerance says where it comes from.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_granite_hybrid import (ROW, documents, pack, relative,
                                       token_file)
from video_features_tpu.models import deepseek_v2 as ds
from video_features_tpu.reference import deepseek_v2 as ref

pytestmark = pytest.mark.quick

PUBLISHED_ROPE = dict(beta_fast=32, beta_slow=1, factor=40, mscale=0.707,
                      mscale_all_dim=0.707,
                      original_max_position_embeddings=4096, type="yarn")
TINY = dict(
    hidden_size=64, num_hidden_layers=3, vocab_size=512, rms_norm_eps=1e-6,
    num_attention_heads=4, num_key_value_heads=4, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32, q_lora_rank=None,
    rope_theta=10000,
    rope_scaling=dict(PUBLISHED_ROPE, factor=4, beta_fast=4,
                      original_max_position_embeddings=16),
    intermediate_size=96, moe_intermediate_size=32, n_routed_experts=8,
    n_shared_experts=2, num_experts_per_tok=3, first_k_dense_replace=1,
    moe_layer_freq=1, norm_topk_prob=False, routed_scaling_factor=1,
    scoring_func="softmax", topk_method="greedy", hidden_act="silu",
    attention_bias=False)
SEGMENTS = 4      # lines the step returns per row (a row is ROW = 96 tokens)

#: float32 program against the float32 reference: both sum the same few
#: hundred terms in another order; measured 3e-7 to 6e-7 of the largest
#: state, held to 1e-5 as the issue asks
F32_BAND = 1e-5
#: bfloat16 program (weights rounded once, activations bfloat16, float32
#: softmax, router, angles and norms) against the reference on the unrounded
#: weights: measured 5.6e-3 to 7.1e-3 of the largest state over three seeds.
#: Three times the largest reading; what the tests below leave out of the
#: model reads 2e-2 and up in float32
BF16_BAND = 2.2e-2


@pytest.fixture(scope="module")
def arch():
    return ds.arch_from_config(TINY)


def reference_weights(arch, seed=0):
    layers = [ds.layer_weights(arch, seed, i)
              for i in range(arch.num_hidden_layers)]
    return (lambda i: layers[i]), ds.outer_weights(arch, seed)


@pytest.fixture(scope="module")
def weights(arch):
    return reference_weights(arch)


@pytest.fixture(scope="module")
def step(arch):
    """``step(rows, dtype=float32)`` -> the per-token states, the routers'
    choices and the step's pooled lines of packed rows."""
    cache = {}

    def run(rows, dtype=jnp.float32):
        key = (jnp.dtype(dtype), rows.shape)
        if key not in cache:
            params = ds.init_params(arch, 0, dtype)

            def fn(p, r):
                f, chosen = ds.token_states(arch, p, r, dtype)
                return f, chosen, ds.pool_segments(
                    ds.FAMILY, arch.n_routed_experts, SEGMENTS, r[:, 1], f,
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


def test_float32_model_is_the_reference_token_by_token(step, truth):
    docs, wanted = truth
    f, chosen, _ = step(pack(docs))
    assert chosen.shape == (2, 1, ROW, 3)       # the dense layer routes none
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


# -- (2) packing: the mask and the restarted positions ----------------------------

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
    assert lines[0, i, hidden:].sum() == 2 * 3 * len(docs[i])


def test_positions_restart_at_every_segment_and_after_padding():
    from video_features_tpu.models.token_rows import segment_positions
    seg = np.array([[1, 1, 1, 2, 2, 3, 0, 0], [0, 0, 1, 1, 1, 1, 2, 0]])
    got = np.asarray(segment_positions(jnp.asarray(seg, jnp.int32)))
    assert got[0, :6].tolist() == [0, 1, 2, 0, 1, 0]
    assert got[1, 2:7].tolist() == [0, 1, 2, 3, 0]


# -- (3) YaRN's numbers -------------------------------------------------------------

def test_yarn_numbers_for_the_published_keys():
    arch = ds.arch_from_config(dict(
        TINY, qk_nope_head_dim=128, qk_rope_head_dim=64,
        rope_scaling=PUBLISHED_ROPE))
    assert ds.yarn_bounds(arch) == (10, 23)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert ds.softmax_scale(arch) == pytest.approx(192 ** -0.5 * m * m,
                                                   rel=1e-12)
    assert 192 ** -0.5 == pytest.approx(0.0721688, rel=1e-6)
    assert m * m == pytest.approx(1.5896262, rel=1e-7)
    assert ds.rotary_scale(arch) == 1.0
    got = ds.yarn_inv_freq(arch)
    assert got.shape == (32,)
    for i in (0, 10, 16, 23, 31):
        f = 10000.0 ** (-2 * i / 64)
        ramp = min(max((i - 10) / 13, 0.0), 1.0)
        assert got[i] == pytest.approx(f * (1 - ramp) + f / 40 * ramp,
                                       rel=1e-12), i
    assert got[10] == pytest.approx(10000.0 ** (-20 / 64), rel=1e-12)
    assert got[23] == pytest.approx(10000.0 ** (-46 / 64) / 40, rel=1e-12)
    # the reference computes them on its own
    assert np.allclose(ref.inv_freq(arch), got, rtol=1e-12, atol=0)
    assert ref.softmax_scale(arch) == pytest.approx(ds.softmax_scale(arch))


def test_the_tiny_yarn_is_neither_the_plain_rope_nor_all_interpolated(arch):
    low, high = ds.yarn_bounds(arch)
    assert 0 <= low < high <= 3
    plain = 10000.0 ** (-2 * np.arange(4) / 8)
    got = ds.yarn_inv_freq(arch)
    assert got[0] == plain[0] and got[-1] == pytest.approx(plain[-1] / 4)
    assert max(DOCS) > arch.rope_original_max_position_embeddings


def test_what_the_model_cannot_run_is_refused():
    for changed in (dict(q_lora_rank=1536),
                    dict(rope_scaling=None),
                    dict(scoring_func="sigmoid"),
                    dict(topk_method="group_limited_greedy"),
                    dict(num_key_value_heads=2),
                    dict(num_hidden_layers=1)):
        with pytest.raises(NotImplementedError, match="deepseek_v2"):
            ds.arch_from_config(dict(TINY, **changed))
    with pytest.raises(ValueError, match="layer_shards"):
        ds.arch_from_config(TINY, layer_shards=3)


# -- (4) the comparison notices -------------------------------------------------------

@pytest.mark.parametrize("what", ["the rotary left out",
                                  "m squared left out of the scale",
                                  "the gate renormalised"])
def test_the_comparison_notices(arch, weights, step, truth, what):
    """The reference with one thing changed differs from the float32 program
    by a hundred times the band that holds the two together, on the row's
    second document (which starts at position 40 of the row). Measured:
    4.9e-3 without the rotary (weights of 0.02 make near-flat softmaxes, so
    what moves the scores moves the states little), 6.3e-3 without m
    squared, 2.2e-1 with the gate renormalised."""
    docs, wanted = truth
    doc = docs[1]
    changed = {
        "the rotary left out": dict(positions=np.zeros(len(doc))),
        "m squared left out of the scale":
            dict(scale=(arch.qk_head_dim) ** -0.5),
        "the gate renormalised": dict(renormalise=True),
    }[what]
    wrong, _ = plainly(arch, weights, doc, **changed)
    f, _, _ = step(pack(docs))
    got = list(segments_of(f, docs))[1]
    assert relative(got, wanted[1][0]) < F32_BAND
    assert relative(got, wrong) > 100 * F32_BAND, what


def test_positions_that_run_on_across_documents_cannot_be_noticed(
        arch, weights, step, truth):
    """The issue asked for the comparison to notice positions that are not
    restarted at a document. It cannot: a rotary embedding enters the scores
    only through the difference of two positions, and the mask keeps both in
    one document, so the reference at positions 40 .. 63 is the reference at
    0 .. 23 but for the rounding of larger angles (float32 angles near
    position 16,383 carry 1e-3 rad of error, which these near-flat softmaxes
    do not show either). Restarting keeps the published positions and a
    short document's angles exact."""
    docs, wanted = truth
    shifted, _ = plainly(arch, weights, docs[1],
                         positions=len(docs[0]) + np.arange(len(docs[1])))
    assert relative(shifted, wanted[1][0]) < F32_BAND
    far, _ = plainly(arch, weights, docs[1],
                     positions=16384 - len(docs[1]) + np.arange(len(docs[1])))
    assert relative(far, wanted[1][0]) < F32_BAND


# -- (5) the chip's share ---------------------------------------------------------------

def test_the_two_expert_shares_add_up_to_the_uncut_layer(arch):
    """With ``layer_shards`` 2 the routed parts of the two shares plus the
    shared experts, counted once, are the uncut reference's layer."""
    (doc,) = documents(3, (33,))
    u = jnp.asarray(np.random.default_rng(3).normal(size=(33, 64)),
                    jnp.float32)
    whole = ds.layer_weights(arch, 0, 1)
    with jax.default_matmul_precision("highest"):
        want, want_chosen = ref.experts(arch, whole, u, False)
        shared = ref.gated(u, whole["shared_in"], whole["shared_out"])
        total = shared
        for rank in (0, 1):
            part = ds.arch_from_config(TINY, layer_shards=2,
                                       layer_shard_rank=rank)
            assert (part.first_expert, part.experts_held) == (4 * rank, 4)
            w = ds.layer_weights(part, 0, 1)
            # expert e is the same matrix whichever share holds it
            assert np.array_equal(w["experts_in"],
                                  whole["experts_in"][4 * rank:4 * rank + 4])
            out, chosen = ref.experts(part, w, u, False)
            assert np.array_equal(chosen, want_chosen)
            total = total + (out - shared)
            # and the program's share is the reference's share
            gates, picks = ds.moe.route(u, w["router"], 3,
                                        rule="softmax_topk",
                                        renormalise=False)
            routed = ds.moe.held_experts(
                u, gates, picks, w["experts_in"], w["experts_out"],
                part.first_expert, jnp.ones((33,), bool), 8)
            assert relative(np.asarray(routed), np.asarray(out - shared)) \
                < F32_BAND
    assert relative(np.asarray(total), np.asarray(want)) < F32_BAND


# -- the normal path ------------------------------------------------------------------------

def tiny_keys(tmp, **more):
    keys = dict(
        feature_type="deepseek_v2", architecture=dict(TINY), device="cpu", allow_random_weights=True, stack_size=ROW,
        batch_size=2, max_segments=SEGMENTS, on_extraction="save_numpy",
        output_path=str(tmp / "out"), tmp_path=str(tmp / "tmp"))
    keys.update(more)
    return keys


@pytest.fixture(scope="module")
def extractor(tmp_path_factory):
    from video_features_tpu.config import load_config, sanity_check
    from video_features_tpu.registry import get_extractor_cls
    args = load_config("deepseek_v2",
                       tiny_keys(tmp_path_factory.mktemp("deepseek")))
    sanity_check(args, require_videos=False)
    return get_extractor_cls("deepseek_v2")(args)


def test_the_extractor_agrees_with_the_reference_window_by_window(
        extractor, weights, tmp_path):
    (doc,) = documents(4, (230,))
    got = extractor.extract(token_file(tmp_path / "long.tokens", doc))
    assert got["deepseek_v2"].shape == (3, 64)          # ceil(230 / 96)
    assert got["deepseek_v2"].dtype == np.float32
    assert got["expert_tokens"].shape == (3, 2, 8)
    with jax.default_matmul_precision("highest"):
        feats, counts = ref.features(extractor.arch, *weights, doc, ROW, ROW)
    assert relative(got["deepseek_v2"], feats) < F32_BAND
    assert np.array_equal(got["expert_tokens"], counts)
    assert got["expert_tokens"].sum(axis=(1, 2)).tolist() == [
        2 * 3 * 96, 2 * 3 * 96, 2 * 3 * 38]
    empty = extractor.extract(token_file(tmp_path / "none.tokens", []))
    assert empty["deepseek_v2"].shape == (0, 64)
    with pytest.raises(ValueError, match="vocabulary rows held"):
        extractor.extract(token_file(tmp_path / "bad.tokens", [3, 512]))
    with pytest.raises(NotImplementedError, match=".tokens"):
        extractor.extract(str(tmp_path / "clip.mp4"))


def test_a_document_states_mla_once_and_counts_its_assignments(
        extractor, tmp_path):
    """The ``mla`` event on the first item's span, ``moe.assignments``,
    ``packer.pair_fill`` and ``attention.blocks`` on the program's own
    timeline."""
    from video_features_tpu.telemetry import trace
    from video_features_tpu.telemetry.spans import VideoSpan
    from video_features_tpu.utils.profiling import profiler
    (doc,) = documents(7, (130,))
    path = token_file(tmp_path / "doc.tokens", doc)
    extractor._mla_stated = False
    profiler.set_trace_hook(lambda name, t0, dt: None)  # records in memory
    try:
        with VideoSpan(path) as span:
            got = extractor.extract(path)
            extractor.extract(path)
    finally:
        profiler.set_trace_hook(None)
    stated = [e for e in span.record["events"] if e["kind"] == "mla"]
    assert len(stated) == 1
    assert {k: stated[0][k] for k in ("form", "qk_head_dim", "v_head_dim",
                                      "kv_lora_rank", "rope", "factor")} == {
        "form": "expanded", "qk_head_dim": 24, "v_head_dim": 16,
        "kv_lora_rank": 32, "rope": "yarn", "factor": 4.0}
    assert stated[0]["softmax_scale"] == pytest.approx(
        ds.softmax_scale(extractor.arch))
    events = [e for e in trace.last_recording().events()
              if e.get("ph") == "C"]
    assigned = [e["args"] for e in events if e["name"] == "moe.assignments"]
    a_layer = got["expert_tokens"].sum(axis=0)
    assert assigned[:2] == [{"held": int(a_layer.sum(axis=1).max())},
                            {"all": 130 * 3}]
    # two sealed rows a document: 96 tokens of one segment, then 34
    pairs = [e["args"] for e in events if e["name"] == "packer.pair_fill"]
    assert pairs[:4] == [{"pairs": 96 * 97 // 2}, {"capacity": 96 * 96},
                         {"pairs": 34 * 35 // 2}, {"capacity": 96 * 96}]
    # ... and beside each, the block pairs its attention folds: a row of
    # 96 tokens is one tile against one block
    blocks = [e["args"] for e in events if e["name"] == "attention.blocks"]
    assert blocks[:4] == [{"kept": 1}, {"total": 1}] * 2


def test_the_first_item_states_how_its_grouped_products_run(extractor,
                                                            tmp_path):
    """One ``moe`` event on the first item's span (the token families'
    common half): on the CPU the products are ``ragged_dot`` and the
    kernel's gate says why."""
    from video_features_tpu.telemetry.spans import VideoSpan
    (doc,) = documents(8, (50,))
    path = token_file(tmp_path / "doc.tokens", doc)
    extractor._moe_stated = False
    with VideoSpan(path) as span:
        extractor.extract(path)
        extractor.extract(path)
    (stated,) = [e for e in span.record["events"] if e["kind"] == "moe"]
    assert {k: stated[k] for k in ("products", "rows", "experts", "widths",
                                   "tiles")} == {
        "products": "ragged_dot", "rows": 2 * ROW * 3, "experts": 8,
        "widths": [[64, 64], [32, 64]], "tiles": None}
    assert "cpu" in stated["fallback"]


def test_serve_loop_turns_token_files_into_feature_files(tmp_path):
    """The normal path: ``vft-serve`` over a spool of requests whose items
    are token files, two workers packing into shared rows."""
    import threading

    from video_features_tpu import serve
    docs = documents(6, (40, 150, 96, 9))
    paths = [token_file(tmp_path / f"doc{i}.tokens", d)
             for i, d in enumerate(docs)]
    spool = str(tmp_path / "spool")
    keys = tiny_keys(tmp_path, spool_dir=spool, serve_workers=2,
                     serve_poll_interval_s=0.05, serve_max_requests=2,
                     metrics_interval_s=1)
    keys.pop("architecture")
    flat = {k: v for k, v in TINY.items() if k != "rope_scaling"}
    flat.update({f"rope_scaling.{k}": v
                 for k, v in TINY["rope_scaling"].items()})
    argv = [f"{k}={v}" for k, v in keys.items()] + [
        f"architecture.{k}={'null' if v is None else v}"
        for k, v in flat.items()]
    server = threading.Thread(target=serve.serve_main, args=(argv,),
                              daemon=True)
    server.start()
    rids = [serve.submit_request(spool, paths[:2]),
            serve.submit_request(spool, paths[2:])]
    for rid in rids:
        response = serve.wait_response(spool, rid, timeout_s=120)
        assert response["status"] == "done", response
    server.join(timeout=60)
    out = tmp_path / "out" / "deepseek_v2" / "DeepSeek-V2-Lite"
    arch = ds.arch_from_config(TINY)
    weights = reference_weights(arch)
    for i, doc in enumerate(docs):
        feats = np.load(out / f"doc{i}_deepseek_v2.npy")
        counts = np.load(out / f"doc{i}_expert_tokens.npy")
        with jax.default_matmul_precision("highest"):
            want, want_counts = ref.features(arch, *weights, doc, ROW, ROW)
        assert relative(feats, want) < F32_BAND
        assert np.array_equal(counts, want_counts)
