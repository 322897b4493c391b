"""Ring / all-to-all sequence parallelism vs dense attention, 8-dev CPU mesh."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from video_features_tpu.parallel.sequence import (dense_attention,
                                                  ring_attention,
                                                  ulysses_attention)


def _qkv(rng, b=2, t=64, h=8, d=16):
    mk = lambda: jnp.asarray(rng.normal(size=(b, t, h, d)).astype(np.float32))
    return mk(), mk(), mk()


@pytest.fixture(scope="module")
def seq_mesh():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    from jax.sharding import Mesh
    return Mesh(np.array(devs[:8]), ("seq",))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(rng, seq_mesh, causal):
    q, k, v = _qkv(rng)
    ref = np.asarray(dense_attention(q, k, v, causal=causal))
    out = np.asarray(ring_attention(q, k, v, mesh=seq_mesh, causal=causal))
    assert out.shape == ref.shape
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_dense(rng, seq_mesh, causal):
    q, k, v = _qkv(rng)
    ref = np.asarray(dense_attention(q, k, v, causal=causal))
    out = np.asarray(ulysses_attention(q, k, v, mesh=seq_mesh, causal=causal))
    assert out.shape == ref.shape
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_ring_attention_long_sequence_memory_shape(rng, seq_mesh):
    """T=1024 over 8 devices: per-device block is 128 — the score matrix a
    device materializes is (128, 1024/8) per step, never (1024, 1024)."""
    q, k, v = _qkv(rng, b=1, t=1024, h=2, d=8)
    ref = np.asarray(dense_attention(q, k, v))
    out = np.asarray(ring_attention(q, k, v, mesh=seq_mesh))
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


def test_blockwise_attention_matches_dense(rng):
    """Single-device FlashAttention-style recurrence: exact vs dense for
    causal and non-causal, block-divisible and ragged T, block >= T."""
    from video_features_tpu.parallel.sequence import (blockwise_attention,
                                                      dense_attention)
    for t, bs in ((32, 8), (37, 8), (16, 64)):
        q, k, v = (jnp.asarray(rng.normal(size=(2, t, 3, 8))
                               .astype(np.float32)) for _ in range(3))
        for causal in (False, True):
            got = blockwise_attention(q, k, v, block_size=bs, causal=causal)
            want = dense_attention(q, k, v, causal=causal)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-5, atol=2e-5,
                                       err_msg=f"t={t} bs={bs} causal={causal}")


def _dense_segments(q, k, v, seg, scale, causal=True):
    """Dense (causal) attention within segments, values as wide as they
    are."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    t = q.shape[1]
    mask = (jnp.tril(jnp.ones((t, t), bool)) if causal
            else jnp.ones((t, t), bool))[None, None] \
        & (seg[:, None, :, None] == seg[:, None, None, :])
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("t, bs", [(32, 8), (37, 8), (16, 64)])
@pytest.mark.parametrize("segments", [False, True])
def test_blockwise_attention_with_values_narrower_than_keys(rng, t, bs,
                                                            segments):
    """deepseek_v2's latent attention: 24-wide query/key heads (a width
    that is no multiple of 16), 16-wide value heads; causal, with and without
    packed segments, T not a multiple of the block, a scale of its own."""
    from video_features_tpu.parallel.sequence import blockwise_attention
    q, k = (jnp.asarray(rng.normal(size=(2, t, 3, 24)).astype(np.float32))
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(2, t, 3, 16)).astype(np.float32))
    seg = jnp.asarray(np.sort(rng.integers(1, 4, (2, t)), axis=1), jnp.int32) \
        if segments else jnp.ones((2, t), jnp.int32)
    got = blockwise_attention(q, k, v, block_size=bs, causal=True,
                              scale=0.11,
                              segment_ids=seg if segments else None)
    assert got.shape == (2, t, 3, 16)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_dense_segments(q, k, v, seg, 0.11)),
        rtol=2e-5, atol=2e-5)


# -- blockwise_attention computes only the (tile, block) pairs the mask can keep --

def _packed_qkv(rng, b, t, h=3, d=24, dv=16):
    q, k = (jnp.asarray(rng.normal(size=(b, t, h, d)).astype(np.float32))
            for _ in range(2))
    return q, k, jnp.asarray(rng.normal(size=(b, t, h, dv))
                             .astype(np.float32))


def _segment_ids(rng, kind, b, t):
    """Rows whose documents differ: ``sorted`` as the packer lays a row out
    (1, 2, ... then padding, 0, at its end), ``shuffled`` any ids at all."""
    if kind is None:
        return None
    seg = rng.integers(1, 6, (b, t))
    if kind == "sorted":
        seg = np.sort(seg, axis=1)
        for row, pad in zip(seg, rng.integers(0, t // 3, b)):
            row[t - pad:] = 0
    return seg.astype(np.int32)


@pytest.mark.parametrize("b", [1, 3])
@pytest.mark.parametrize("q_tile, bs", [(4, 8), (8, 8), (16, 8)])
@pytest.mark.parametrize("segments", [None, "sorted", "shuffled"])
@pytest.mark.parametrize("causal", [False, True])
def test_tiled_blockwise_attention_matches_dense(rng, monkeypatch, causal,
                                                 segments, q_tile, bs, b):
    """Query tiles below, equal to and above the key block, T (75) a multiple
    of neither, values narrower than keys, one row and three whose documents
    differ; unmasked, it is the scan over every block."""
    from video_features_tpu.parallel import sequence
    monkeypatch.setattr(sequence, "Q_TILE", q_tile)
    t = 75
    q, k, v = _packed_qkv(rng, b, t)
    seg = _segment_ids(rng, segments, b, t)
    got = sequence.blockwise_attention(
        q, k, v, block_size=bs, causal=causal, scale=0.11,
        segment_ids=None if seg is None else jnp.asarray(seg))
    assert got.shape == (b, t, 3, 16)
    want = _dense_segments(
        q, k, v, jnp.ones((b, t), jnp.int32) if seg is None
        else jnp.asarray(seg), 0.11, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("b", [1, 3])
@pytest.mark.parametrize("segments", [None, "sorted", "shuffled"])
def test_skipping_a_block_is_exact(rng, monkeypatch, segments, b):
    """With its bounds forced open the function folds every block, as it did
    before it had bounds: the same bits, the skipped folds were identities."""
    from video_features_tpu.parallel import sequence
    monkeypatch.setattr(sequence, "Q_TILE", 16)
    t, bs = 75, 8
    q, k, v = _packed_qkv(rng, b, t)
    seg = _segment_ids(rng, segments, b, t)

    def run():
        return np.asarray(sequence.blockwise_attention(
            q, k, v, block_size=bs, causal=True, scale=0.11,
            segment_ids=None if seg is None else jnp.asarray(seg)))

    lo, hi = sequence.block_bounds(seg, t, 16, bs, True)
    assert int((hi - lo).sum()) < lo.size * 10      # something is skipped
    skipping = run()
    monkeypatch.setattr(
        sequence, "block_bounds", lambda ids, t, q_tile, block_size, causal:
        (np.zeros((1, 5), np.int32), np.full((1, 5), 10, np.int32)))
    assert np.array_equal(run(), skipping)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("segments", ["sorted", "shuffled"])
@pytest.mark.parametrize("q_tile, bs", [(8, 16), (16, 16), (32, 16)])
def test_no_block_outside_the_bounds_holds_a_valid_pair(rng, segments, causal,
                                                        q_tile, bs):
    from video_features_tpu.parallel.sequence import block_bounds
    b, t = 4, 150
    seg = _segment_ids(rng, segments, b, t)
    lo, hi = block_bounds(seg, t, q_tile, bs, causal)
    n_tiles, n_blocks = -(-t // q_tile), -(-t // bs)
    assert lo.shape == hi.shape == (b, n_tiles)
    pos = np.arange(t)
    mask = seg[:, :, None] == seg[:, None, :]
    if causal:
        mask = mask & (pos[:, None] >= pos[None, :])
    inside = 0
    for r in range(b):
        for j in range(n_tiles):
            for i in range(n_blocks):
                held = mask[r, j * q_tile:(j + 1) * q_tile,
                            i * bs:(i + 1) * bs].any()
                if not lo[r, j] <= i < hi[r, j]:
                    assert not held, (r, j, i)
                inside += int(held)
    assert inside <= int((hi - lo).sum()) <= b * n_tiles * n_blocks
    # the device's table is the host's
    on_device = block_bounds(jnp.asarray(seg), t, q_tile, bs, causal)
    assert all(isinstance(x, jax.Array) for x in on_device)
    assert np.array_equal(on_device[0], lo) and np.array_equal(on_device[1], hi)


def test_a_causal_document_of_16384_tokens_keeps_528_of_1024_blocks():
    from video_features_tpu.parallel.sequence import (BLOCK_SIZE, Q_TILE,
                                                      block_bounds)
    assert (Q_TILE, BLOCK_SIZE) == (512, 512)
    for ids in (None, np.ones((1, 16384), np.int32)):
        lo, hi = block_bounds(ids, 16384, Q_TILE, BLOCK_SIZE, True)
        assert lo.shape == (1, 32) and not lo.any()
        assert hi[0].tolist() == list(range(1, 33))       # sum: 528
    # two documents in the row: the second one's tiles start at its blocks
    ids = np.repeat([1, 2], [6144, 10240])[None].astype(np.int32)
    lo, hi = block_bounds(ids, 16384, Q_TILE, BLOCK_SIZE, True)
    assert int((hi - lo).sum()) == 12 * 13 // 2 + 20 * 21 // 2
    # nothing to skip without a mask
    lo, hi = block_bounds(None, 16384, Q_TILE, BLOCK_SIZE, False)
    assert int((hi - lo).sum()) == 1024


@pytest.mark.parametrize("heads, t, tile, mb", [
    (32, 16384, 512, 33.55),    # lfm2's row: 32 heads of 64
    (32, 4096, 512, 33.55),     # granite's
    (16, 16384, 512, 16.78),    # dsv2's
    (4, 96, 96, 0.15)])         # a row shorter than a tile is one tile
def test_the_stated_tile_is_the_one_the_loop_scores(heads, t, tile, mb):
    from video_features_tpu.parallel.sequence import stated_tile
    assert stated_tile(heads, t) == {"q_tile": tile, "block_size": tile,
                                     "heads": heads, "score_tile_mb": mb}


def _scan_over_every_block(q, k, v, block_size, scale):
    """``blockwise_attention`` as it was before it had tiles (PR 32), without
    a mask: what the unmasked call still has to be."""
    from video_features_tpu.parallel.sequence import (_fold_finalize,
                                                      _fold_init,
                                                      _softmax_fold)
    b, t, h, d = q.shape
    bs = min(block_size, t)
    n_blocks = -(-t // bs)
    pad = n_blocks * bs - t
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = jnp.moveaxis(kp.reshape(b, n_blocks, bs, h, d), 1, 0)
    vb = jnp.moveaxis(vp.reshape(b, n_blocks, bs, h, d), 1, 0)

    def step(acc, blk):
        o, m, l, i = acc
        k_pos = i * bs + jnp.arange(bs)
        o, m, l = _softmax_fold(q, (o, m, l), blk[0], blk[1], scale,
                                k_pos[None, :] < t)
        return (o, m, l, i + 1), None

    o0, m0, l0 = _fold_init(b, h, t, d)
    (o, _, l, _), _ = jax.lax.scan(step, (o0, m0, l0, 0), (kb, vb))
    return _fold_finalize(o, l, q.dtype)


@pytest.mark.parametrize("t, bs", [(300, 256), (37, 8), (16, 64)])
def test_unmasked_it_is_the_one_scan_it_was(rng, t, bs):
    """CLIP's ``vision_attn: blockwise``: no ``while`` in the program, the
    old scan's operations, the old scan's bits."""
    from video_features_tpu.parallel.sequence import blockwise_attention
    q, k, v = _qkv(rng, b=2, t=t, h=3, d=8)
    new = jax.make_jaxpr(lambda *a: blockwise_attention(
        *a, block_size=bs, scale=1.0))(q, k, v)
    old = jax.make_jaxpr(lambda *a: _scan_over_every_block(
        *a, block_size=bs, scale=1.0))(q, k, v)
    assert "while" not in str(new) and "scan" in str(new)
    assert str(new) == str(old)
    assert "while" in str(jax.make_jaxpr(lambda *a: blockwise_attention(
        *a, block_size=bs, causal=True))(q, k, v))
    assert np.array_equal(
        np.asarray(blockwise_attention(q, k, v, block_size=bs, scale=1.0)),
        np.asarray(_scan_over_every_block(q, k, v, bs, 1.0)))


@pytest.mark.parametrize("segments", [False, True])
def test_the_key_and_value_blocks_are_made_before_the_loops(rng, segments):
    """Grouped-query keys and values repeated to every head: the blocks the
    loops index pass an optimization barrier before the scan, so nothing
    that makes them can be sunk into it (``tests/test_kernels_v5e_compile.py``
    holds the compiled loops to it at the token cells' widths)."""
    from video_features_tpu.parallel.sequence import blockwise_attention
    q = jnp.asarray(rng.normal(size=(1, 64, 4, 8)).astype(np.float32))
    k, v = (jnp.repeat(jnp.asarray(rng.normal(size=(1, 64, 2, 8))
                                   .astype(np.float32)), 2, axis=2)
            for _ in range(2))
    seg = jnp.asarray(np.repeat([1, 2], 32)[None].astype(np.int32))
    jaxpr = jax.make_jaxpr(lambda q, k, v: blockwise_attention(
        q, k, v, block_size=16, causal=True,
        segment_ids=seg if segments else None))(q, k, v).jaxpr
    kinds = [e.primitive.name for e in jaxpr.eqns]
    assert kinds.index("optimization_barrier") < kinds.index("scan")


@pytest.mark.parametrize("segments", [False, True])
def test_a_fully_masked_tile_stays_finite(rng, monkeypatch, segments):
    """T = 65 under tiles of 16: the last tile is one query and fifteen
    padded ones, which match no key where there are segments; a row that is
    all padding (segment 0 throughout) beside a packed one. No NaN anywhere
    in the program, the padded tile's lines included."""
    from video_features_tpu.parallel import sequence
    monkeypatch.setattr(sequence, "Q_TILE", 16)
    t = 65
    q, k, v = _packed_qkv(rng, 2, t)
    seg = np.stack([np.zeros(t, np.int32),
                    np.repeat([1, 2, 0], [30, 25, 10]).astype(np.int32)])
    with jax.debug_nans(True):
        got = np.asarray(sequence.blockwise_attention(
            q, k, v, block_size=8, causal=True, scale=0.11,
            segment_ids=jnp.asarray(seg) if segments else None))
    assert np.isfinite(got).all()
    want = _dense_segments(q, k, v, jnp.asarray(seg) if segments
                           else jnp.ones((2, t), jnp.int32), 0.11)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-5, atol=2e-5)


def _guarded_fold(q, acc, ck, cv, scale, valid):
    """The fold as it was before it trusted ``exp(-inf) = 0``: it set every
    masked element of ``p`` to 0 again, from the scores' infinities."""
    o, m, l = acc
    s = jnp.einsum("bqhd,bkhd->bhqk", q, ck,
                   preferred_element_type=jnp.float32) * scale
    if valid is not None:
        s = jnp.where(valid, s, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
    p = jnp.exp(s - m_safe)
    if valid is not None:
        p = jnp.where(jnp.isinf(s), 0.0, p)
    alpha = jnp.where(jnp.isinf(m), 0.0, jnp.exp(m - m_safe))
    o = o * alpha + jnp.einsum("bhqk,bkhd->bhqd", p, cv,
                               preferred_element_type=jnp.float32)
    l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    return o, m_new, l


#: (t, causal, segment ids): a causal row, shuffled documents with no causal
#: mask, sorted documents with padding (0) at the row's end, no mask at all
#: (keys padded to a whole block), and T = 65 under tiles of 16, whose last
#: tile is one query and fifteen padded ones, beside a row all padding
@pytest.mark.parametrize("t, causal, segments", [
    (75, True, None), (75, False, "shuffled"), (75, True, "sorted"),
    (75, False, None), (65, True, "all padding"), (65, True, None)],
    ids=["causal", "segmented", "padded", "unmasked", "masked-tile-segments",
         "masked-tile-causal"])
def test_the_fold_gives_the_bits_it_gave_with_its_isinf_guard(
        rng, monkeypatch, t, causal, segments):
    """A masked score is -inf and the shift ``m_safe`` is finite (0 on a row
    with no key yet), so ``exp(s - m_safe)`` is already 0 where the mask
    holds: the same bits as the fold that zeroed ``p`` from ``isinf(s)``,
    under ``debug_nans`` where a tile holds no valid pair."""
    from video_features_tpu.parallel import sequence
    monkeypatch.setattr(sequence, "Q_TILE", 16)
    q, k, v = _packed_qkv(rng, 2, t)
    seg = (np.stack([np.zeros(t, np.int32),
                     np.repeat([1, 2, 0], [30, 25, 10]).astype(np.int32)])
           if segments == "all padding" else
           _segment_ids(rng, segments, 2, t))

    def run():
        with jax.debug_nans(True):
            return np.asarray(sequence.blockwise_attention(
                q, k, v, block_size=8, causal=causal, scale=0.11,
                segment_ids=None if seg is None else jnp.asarray(seg)))

    got = run()
    monkeypatch.setattr(sequence, "_softmax_fold", _guarded_fold)
    assert np.isfinite(got).all()
    assert np.array_equal(got, run())


@pytest.mark.parametrize("row_len, lengths, want", [
    # one tile, one block: nothing to skip
    (96, (96, 34), [(1, 1), (1, 1)]),
    # three tiles of 512 over three blocks; a document a row, then a row of
    # two whose second starts in the second block, then one with padding
    (1536, (1536, 600, 936, 1100), [(6, 9), (5, 9), (6, 9)]),
])
def test_the_packer_counts_the_blocks_attention_will_run(row_len, lengths,
                                                         want):
    """``attention.blocks`` (``kept``, ``total``) beside ``packer.pair_fill``
    on the program's own timeline, a pair of samples a sealed row, from the
    table the device computes its loop bounds from."""
    from video_features_tpu.parallel.packer import SegmentPacker
    from video_features_tpu.parallel.sequence import blocks_run
    from video_features_tpu.telemetry import trace

    class Runner:
        fixed_batch = 1

        def dispatch(self, group):
            return np.zeros((len(group), 4, 1), np.float32)

    recorder = trace.TraceRecorder(None).start()
    try:
        packer = SegmentPacker(Runner(), batch=1, row_len=row_len,
                               max_segments=4)
        handle = packer.open_video()
        for n in lengths:
            packer.add(handle, np.ones(n, np.int32))
        packer.close_video(handle)
    finally:
        recorder.close()
    events = [e for e in trace.last_recording().events()
              if e.get("ph") == "C"]
    names = [e["name"] for e in events if e["name"].startswith(
        ("packer.pair_fill", "attention.blocks"))]
    assert names == (["packer.pair_fill"] * 2
                     + ["attention.blocks"] * 2) * len(want)
    blocks = [e["args"] for e in events if e["name"] == "attention.blocks"]
    assert blocks == [s for kept, total in want
                      for s in ({"kept": kept}, {"total": total})]
    assert all(kept <= total for kept, total in want)
    # the same count from jax arrays, as the device has them
    assert blocks_run(jnp.ones((2, row_len), jnp.int32)) == (
        2 * want[0][0], 2 * want[0][1])
