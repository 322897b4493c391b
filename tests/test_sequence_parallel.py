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


def _dense_segments(q, k, v, seg, scale):
    """Dense causal attention within segments, values as wide as they are."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    t = q.shape[1]
    mask = jnp.tril(jnp.ones((t, t), bool))[None, None] \
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
