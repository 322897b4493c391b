"""granite_hybrid: the model against its plain reference, the chunked scan
against the token-by-token recurrence, packing, the chip's share of the
experts and of the vocabulary, the window cut and the normal path.

Tiny widths (hidden 64, 8 experts top-3 of which 4 are held, state 16, chunk
8), one period of ten layers with one attention layer, seeded weights. Every
tolerance says where it comes from.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from video_features_tpu.models import granite_hybrid as gh
from video_features_tpu.ops import moe, ssd
from video_features_tpu.reference import granite_hybrid as ref

pytestmark = pytest.mark.quick

TINY = dict(
    hidden_size=64, num_hidden_layers=10, vocab_size=512,
    layer_types=["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
    embedding_multiplier=12, residual_multiplier=0.22, logits_scaling=16,
    rms_norm_eps=1e-5, mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
    mamba_d_conv=4, mamba_n_groups=1, mamba_chunk_size=8,
    num_attention_heads=4, num_key_value_heads=2, attention_multiplier=0.0625,
    num_local_experts=8, num_experts_per_tok=3, intermediate_size=24,
    shared_intermediate_size=48, position_embedding_type="nope")
ROW = 96          # tokens a packed row holds in these tests
SEGMENTS = 4      # lines the step returns per row

#: float32 program against the float32 reference: both sum the same few
#: hundred terms in another order; measured 2e-7 to 4e-7 of the largest
#: feature, held to 1e-5 as the issue asks
F32_BAND = 1e-5
#: bfloat16 program (weights rounded once, activations bfloat16, float32
#: state and router) against the reference on the unrounded weights:
#: measured 6.7e-3 to 8.8e-3 relative on pooled features over three seeds,
#: the same per token: the weights' rounding does not average out over
#: tokens. Three times the largest reading; a float8 model reads 0.1 and up
BF16_BAND = 2.5e-2


@pytest.fixture(scope="module")
def arch():
    return gh.arch_from_config(TINY, layer_shards=2, layer_shard_rank=0)


@pytest.fixture(scope="module")
def weights(arch):
    """The reference's float32 weights: ``layer(i)`` and the outer tree."""
    layers = [gh.layer_weights(arch, 0, i)
              for i in range(len(arch.layer_types))]
    return (lambda i: layers[i]), gh.outer_weights(arch, 0)


@pytest.fixture(scope="module")
def step(arch):
    """``step(rows, dtype=float32, state=float32, router=float32)`` -> the
    per-token states, the router's choices and the step's pooled lines of
    packed rows, jitted once per variant."""
    cache = {}

    def run(rows, dtype=jnp.float32, state=jnp.float32, router=jnp.float32):
        key = (jnp.dtype(dtype), jnp.dtype(state), jnp.dtype(router),
               rows.shape)
        if key not in cache:
            params = gh.init_params(arch, 0, dtype)

            def fn(p, r):
                f, chosen = gh.token_states(arch, p, r, dtype, state, router)
                return f, chosen, gh.pool_segments(arch, SEGMENTS, r[:, 1],
                                                   f, chosen)

            cache[key] = (params, jax.jit(fn))
        params, fn = cache[key]
        return tuple(np.asarray(x) for x in fn(params, jnp.asarray(rows)))

    return run


def documents(seed, lengths, vocab=256):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lengths]


def pack(docs, row_len=ROW, lead_pad=0):
    """One row with ``docs`` one after the other as segments 1, 2, ..."""
    row = np.zeros((1, 2, row_len), np.int32)
    at = lead_pad
    for s, doc in enumerate(docs):
        row[0, 0, at:at + len(doc)] = doc
        row[0, 1, at:at + len(doc)] = s + 1
        at += len(doc)
    return row


def relative(a, b):
    return float(np.abs(a - b).max() / np.abs(b).max())


# -- the model against the plain reference -------------------------------------

@pytest.fixture(scope="module")
def truth(arch, weights):
    """Two documents of one packed row (and 16 positions of padding) and
    the reference's states and choices for each, computed once. One length:
    the reference runs operation by operation, and every new shape costs
    seconds of compiling; the packing tests below vary the lengths."""
    docs = documents(1, (40, 40))
    return docs, [tuple(np.asarray(x) for x in
                        ref.token_states(arch, *weights, doc))
                  for doc in docs]


def worst(f, truth, measure):
    docs, wanted = truth
    at, out = 0, 0.0
    for doc, (want, _) in zip(docs, wanted):
        out = max(out, measure(f[0, at:at + len(doc)], want))
        at += len(doc)
    return out


def pooled(got, want):
    return float(np.linalg.norm(got.mean(0) - want.mean(0))
                 / np.linalg.norm(want.mean(0)))


def test_float32_model_is_the_reference_over_a_period(step, truth):
    docs, wanted = truth
    f, chosen, _ = step(pack(docs))
    assert worst(f, truth, relative) < F32_BAND
    at = 0
    for doc, (_, want_chosen) in zip(docs, wanted):
        # the same experts for every token of every layer: a router that
        # disagreed once would show as a 1e-1 error above
        assert np.array_equal(np.sort(chosen[:, 0, at:at + len(doc)], -1),
                              np.sort(want_chosen, -1))
        at += len(doc)


def test_bfloat16_model_is_inside_its_band(step, truth):
    f, _, _ = step(pack(truth[0]), dtype=jnp.bfloat16)
    assert worst(f, truth, pooled) < BF16_BAND


@pytest.mark.parametrize("variant", [
    dict(state=jnp.bfloat16), dict(router=jnp.bfloat16)], ids=["state",
                                                              "router"])
def test_a_bfloat16_state_or_router_moves_the_float32_model(
        step, truth, variant):
    """The float32 model with only its carried scan state, or only its
    router's logits, in bfloat16 reads tens to a thousand times the float32
    model's own distance from the reference (measured on a document of 190
    tokens: 2.6e-7, state 1.7e-5, router 3.2e-4). Beside bfloat16
    activations either is inside the activations' own noise at this size:
    there the two tests of the operations below separate them."""
    rows = pack(truth[0])
    exact = worst(step(rows)[0], truth, relative)
    assert exact < F32_BAND
    assert worst(step(rows, **variant)[0], truth, relative) > 10 * exact


# -- the chunked scan against the token-by-token recurrence -----------------------

def recurrence(x, dt, a, b, c, seg):
    """float64, token by token, the state zeroed at each segment's start."""
    t, h, p = x.shape
    y = np.zeros((t, h, p))
    state = np.zeros((h, p, b.shape[-1]))
    for i in range(t):
        if i == 0 or seg[i] != seg[i - 1]:
            state[:] = 0.0
        state = np.exp(dt[i] * a)[:, None, None] * state \
            + (dt[i][:, None] * x[i])[:, :, None] * b[i][None, None, :]
        y[i] = state @ c[i]
    return y


def scan_inputs(t, seed=0, h=4, p=8, n=16, slow=False):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((t, h, p))
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(3e-3 if slow else 1e-1),
                            (t, h)))
    a = -rng.uniform(1.0, 2.0 if slow else 16.0, h)
    return x, dt, a, rng.standard_normal((t, n)), rng.standard_normal((t, n))


@pytest.mark.parametrize("chunk", [1, 4, 8, 13, 16, 64])
def test_the_chunked_scan_is_the_recurrence_whatever_the_chunk(chunk):
    """29 tokens (no multiple of most chunks) in three segments: boundaries
    at 8 and 16 lie on the edge of chunks of 4 and 8 and inside those of 13
    and 16; the tail is a padding segment. float32 against float64: 1e-5 of
    the largest output."""
    x, dt, a, b, c = scan_inputs(29)
    seg = np.array([1] * 8 + [2] * 8 + [3] * 9 + [0] * 4)
    want = recurrence(x, dt, a, b, c, seg)
    got = ssd.ssd_scan(*(jnp.asarray(v[None], jnp.float32)
                         for v in (x, dt)), jnp.asarray(a, jnp.float32),
                       *(jnp.asarray(v[None], jnp.float32) for v in (b, c)),
                       jnp.asarray(seg[None]), chunk)
    assert relative(np.asarray(got[0]), want) < 1e-5


def test_a_bfloat16_scan_state_fails_where_the_float32_state_passes():
    """512 tokens of one document in chunks of 8, heads that forget slowly
    (dt a of -1e-3 to -6e-3 a token): the state is the sum of hundreds of
    tokens and is handed on 63 times. Inputs in bfloat16 both times. With
    the state in float32 the error is the inputs' rounding; rounding the
    carried state at every chunk adds its own 2**-9 each time."""
    x, dt, a, b, c = scan_inputs(512, slow=True)
    seg = np.ones(512, np.int32)
    rounded = [np.asarray(jnp.asarray(v, jnp.bfloat16).astype(jnp.float32),
                          np.float64) for v in (x, b, c)]
    want = recurrence(rounded[0], dt, a, rounded[1], rounded[2], seg)

    def error(state_dtype):
        got = ssd.ssd_scan(
            jnp.asarray(x[None], jnp.bfloat16), jnp.asarray(dt[None],
                                                            jnp.float32),
            jnp.asarray(a, jnp.float32), jnp.asarray(b[None], jnp.bfloat16),
            jnp.asarray(c[None], jnp.bfloat16), jnp.asarray(seg[None]), 8,
            state_dtype)
        return float(np.linalg.norm(np.asarray(got[0]) - want)
                     / np.linalg.norm(want))

    band = 4e-3
    assert error(jnp.float32) < band < error(jnp.bfloat16)


def test_a_bfloat16_router_swaps_experts_where_the_float32_router_does_not():
    """2,048 tokens of width 256 over 72 experts, top 10, inputs and weights
    exact in bfloat16: float32 logits order the experts as float64 does;
    logits rounded to bfloat16 (8 bits of a value near 0.3) tie or swap the
    tenth and eleventh expert for some percent of the tokens."""
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.standard_normal((2048, 256)), jnp.bfloat16)
    w = jnp.asarray(0.02 * rng.standard_normal((256, 72)), jnp.bfloat16)
    exact = np.asarray(u, np.float64) @ np.asarray(w, np.float64)
    want = np.sort(np.argsort(-exact, axis=1)[:, :10], axis=1)

    def swapped(router_dtype):
        _, chosen = moe.route(u, w, 10, router_dtype)
        return float((np.sort(np.asarray(chosen), 1) != want).any(1).mean())

    assert swapped(jnp.float32) < 0.002 < 0.01 < swapped(jnp.bfloat16)


def test_the_convolution_reads_no_tap_from_another_segment():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((1, 12, 5)).astype(np.float32)
    w = rng.standard_normal((4, 5)).astype(np.float32)
    bias = rng.standard_normal(5).astype(np.float32)
    seg = np.array([[1] * 5 + [2] * 7])
    got = np.asarray(ssd.causal_conv1d(jnp.asarray(x), jnp.asarray(w),
                                       jnp.asarray(bias), jnp.asarray(seg)))
    for start, end in ((0, 5), (5, 12)):
        alone = np.concatenate([np.zeros((3, 5), np.float32), x[0, start:end]])
        want = bias + sum(alone[j:j + end - start] * w[j] for j in range(4))
        assert np.allclose(got[0, start:end], want, atol=1e-6)


# -- packing ---------------------------------------------------------------------

@pytest.mark.parametrize("where", ["first", "last", "after padding",
                                   "between"])
def test_a_document_reads_the_same_wherever_it_is_packed(step, where):
    """Convolution taps, state reset, attention mask and routing: a
    document's states packed among others are those it has alone, to
    float32 rounding (another position in the row is another order of
    summation inside a chunk)."""
    doc, other, third = documents(2, (21, 17, 9))
    alone, alone_chosen, _ = step(pack([doc]))
    rows, at = {"first": (pack([doc, other]), 0),
                "last": (pack([other, third, doc]), 26),
                "after padding": (pack([doc], lead_pad=11), 11),
                "between": (pack([other, doc, third]), 17)}[where]
    if where == "after padding":
        rows[0, 1, 11:32] = 2   # a segment id of its own behind the padding
    f, chosen, _ = step(rows)
    assert relative(f[0, at:at + 21], alone[0, :21]) < F32_BAND
    assert np.array_equal(chosen[:, 0, at:at + 21], alone_chosen[:, 0, :21])


def test_the_step_pools_segments_and_counts_routed_tokens(step, truth):
    docs, wanted = truth
    _, _, lines = step(pack(docs))
    assert lines.shape == (1, SEGMENTS, 64 + 10 * 8)
    for s, (doc, (want, want_chosen)) in enumerate(zip(docs, wanted)):
        assert relative(lines[0, s, :64], want.mean(0)) < F32_BAND
        got = lines[0, s, 64:].reshape(10, 8)
        counts = np.stack([np.bincount(layer.ravel(), minlength=8)
                           for layer in want_chosen])
        assert np.array_equal(got, counts)
        # top-3 of 8 for every token of every layer; padding is in no count
        assert (got.sum(axis=1) == 3 * len(doc)).all()
    assert not lines[0, len(docs):].any()


# -- the chip's share ------------------------------------------------------------

def test_the_two_expert_shares_add_up_to_the_uncut_layer():
    """Experts 0-3 and 4-7, the shared expert counted once, against the
    reference layer that holds all eight; the router's choices are the
    8-wide router's on both chips."""
    whole = gh.arch_from_config(TINY)
    shares = [gh.arch_from_config(TINY, 2, rank) for rank in (0, 1)]
    w = gh.layer_weights(whole, 0, 0)
    u = jnp.asarray(np.random.default_rng(0).standard_normal((40, 64)),
                    jnp.float32)
    want, want_chosen = ref.experts(whole, w, u)
    shared = moe.gated_unit(u, w["shared_in"], w["shared_out"])
    valid = jnp.ones(40, bool)
    total = shared
    for share in shares:
        held = gh.layer_weights(share, 0, 0)
        lo = share.first_expert
        # a share draws the very experts the whole layer holds there
        assert np.array_equal(held["experts_in"],
                              w["experts_in"][lo:lo + share.experts_held])
        gates, chosen = moe.route(u, held["router"],
                                  whole.num_experts_per_tok)
        assert np.array_equal(chosen, want_chosen)
        total = total + moe.held_experts(
            u, gates, chosen, held["experts_in"], held["experts_out"], lo,
            valid, whole.num_local_experts)
    assert relative(np.asarray(total), np.asarray(want)) < F32_BAND


def test_logits_over_the_held_rows_are_those_rows_of_the_full_logits():
    whole = gh.arch_from_config(TINY)
    half = gh.arch_from_config(TINY, 2, 0)
    f = jnp.asarray(np.random.default_rng(0).standard_normal((5, 64)),
                    jnp.float32)
    full = gh.logits(whole, gh.outer_weights(whole, 0), f)
    held = gh.logits(half, gh.outer_weights(half, 0), f)
    assert held.shape == (5, 256) and np.array_equal(held, full[:, :256])
    assert relative(np.asarray(held), np.asarray(ref.logits(
        half, gh.outer_weights(half, 0), f))) < F32_BAND


# -- the extractor: items, windows, the normal path --------------------------------

def tiny_keys(tmp_path, **more):
    keys = dict(feature_type="granite_hybrid", device="cpu",
                architecture=dict(TINY), layer_shards=2, stack_size=ROW,
                batch_size=2, max_segments=SEGMENTS,
                allow_random_weights=True, on_extraction="save_numpy",
                output_path=str(tmp_path / "out"),
                tmp_path=str(tmp_path / "tmp"))
    keys.update(more)
    return keys


@pytest.fixture(scope="module")
def extractor(tmp_path_factory):
    from video_features_tpu.config import load_config, sanity_check
    from video_features_tpu.registry import get_extractor_cls
    args = load_config("granite_hybrid",
                       tiny_keys(tmp_path_factory.mktemp("granite")))
    sanity_check(args, require_videos=False)
    return get_extractor_cls("granite_hybrid")(args)


def token_file(path, ids):
    np.asarray(ids, "<i4").tofile(path)
    return str(path)


def test_an_id_outside_the_held_slice_is_refused_where_the_item_is_read(
        extractor, tmp_path):
    good = token_file(tmp_path / "good.tokens", [0, 255, 7])
    assert extractor.extract(good)["granite_hybrid"].shape == (1, 64)
    for bad in ([3, 256], [-1, 3]):
        with pytest.raises(ValueError, match="vocabulary rows held"):
            extractor.extract(token_file(tmp_path / "bad.tokens", bad))
    with pytest.raises(NotImplementedError, match=".tokens"):
        extractor.extract(str(tmp_path / "clip.mp4"))


def test_a_document_longer_than_a_row_is_cut_into_windows_run_alone(
        extractor, tmp_path):
    (doc,) = documents(4, (230,))
    got = extractor.extract(token_file(tmp_path / "long.tokens", doc))
    assert got["granite_hybrid"].shape == (3, 64)       # ceil(230 / 96)
    assert got["granite_hybrid"].dtype == np.float32
    assert got["expert_tokens"].shape == (3, 10, 8)
    for i, (start, end) in enumerate(((0, 96), (96, 192), (192, 230))):
        alone = extractor.extract(token_file(
            tmp_path / f"w{i}.tokens", doc[start:end]))
        assert relative(got["granite_hybrid"][i],
                        alone["granite_hybrid"][0]) < F32_BAND
        assert np.array_equal(got["expert_tokens"][i],
                              alone["expert_tokens"][0])
        assert got["expert_tokens"][i].sum() == 10 * 3 * (end - start)
    empty = extractor.extract(token_file(tmp_path / "none.tokens", []))
    assert empty["granite_hybrid"].shape == (0, 64)


def test_the_extractor_agrees_with_the_reference(extractor, weights, tmp_path):
    (doc,) = documents(5, (40,))
    got = extractor.extract(token_file(tmp_path / "doc.tokens", doc))
    feats, counts = ref.features(extractor.arch, *weights, doc, ROW, ROW)
    assert relative(got["granite_hybrid"], feats) < F32_BAND
    assert np.array_equal(got["expert_tokens"], counts)


def test_a_document_counts_its_fullest_layers_held_assignments(
        extractor, tmp_path):
    """``moe.assignments``: series ``held`` and ``all``, one sample each per
    document, on the program's own timeline."""
    from video_features_tpu.telemetry import trace
    from video_features_tpu.utils.profiling import profiler
    (doc,) = documents(7, (130,))
    profiler.set_trace_hook(lambda name, t0, dt: None)  # records in memory
    try:
        got = extractor.extract(token_file(tmp_path / "doc.tokens", doc))
        extractor.extract(token_file(tmp_path / "none.tokens", []))
    finally:
        profiler.set_trace_hook(None)
    samples = [e["args"] for e in trace.last_recording().events()
               if e.get("ph") == "C" and e["name"] == "moe.assignments"]
    a_layer = got["expert_tokens"].sum(axis=0)          # (10, 8)
    arch = extractor.arch
    assert (arch.first_expert, arch.experts_held) == (0, 4)
    assert samples == [{"held": int(a_layer[:, :4].sum(axis=1).max())},
                       {"all": 130 * 3}]
    assert 0 < samples[0]["held"] < samples[1]["all"]


def test_serve_loop_turns_token_files_into_feature_files(tmp_path):
    """The normal path: ``vft-serve`` over a spool of requests whose items
    are token files, two workers packing into shared rows, the sinks' stem
    rule."""
    from video_features_tpu import serve
    docs = documents(6, (40, 150, 96, 9))
    paths = [token_file(tmp_path / f"doc{i}.tokens", d)
             for i, d in enumerate(docs)]
    spool = str(tmp_path / "spool")
    keys = tiny_keys(tmp_path, spool_dir=spool, serve_workers=2,
                     serve_poll_interval_s=0.05, serve_max_requests=2,
                     metrics_interval_s=1)
    keys.pop("architecture")
    argv = [f"{k}={v}" for k, v in keys.items()] + [
        f"architecture.{k}={v}" for k, v in TINY.items()
        if k != "layer_types"] + [
        "architecture.layer_types=[" + ",".join(TINY["layer_types"]) + "]"]
    server = threading.Thread(target=serve.serve_main, args=(argv,),
                              daemon=True)
    server.start()
    rids = [serve.submit_request(spool, paths[:2]),
            serve.submit_request(spool, paths[2:])]
    for rid in rids:
        response = serve.wait_response(spool, rid, timeout_s=120)
        assert response["status"] == "done", response
    server.join(timeout=60)
    out = tmp_path / "out" / "granite_hybrid" / "granite-4.0-h-small"
    for i, doc in enumerate(docs):
        feats = np.load(out / f"doc{i}_granite_hybrid.npy")
        counts = np.load(out / f"doc{i}_expert_tokens.npy")
        windows = -(-len(doc) // ROW)
        assert feats.shape == (windows, 64) and np.isfinite(feats).all()
        assert counts.shape == (windows, 10, 8)
        assert counts.sum() == 10 * 3 * len(doc)
