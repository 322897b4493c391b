"""Sequence/context parallelism: ring attention and all-to-all (Ulysses).

The reference has no long-range attention at all — its "sequence" dimension
is video time, scaled by windowing (SURVEY §5: fixed clip stacks, streaming
decode). This module makes long-sequence attention a first-class primitive of
the TPU framework so temporal transformers over thousands of frames (or very
high frame-token counts) shard across a mesh instead of hitting the
single-chip memory wall:

  - :func:`ring_attention` — blockwise attention with the K/V shards rotated
    around the ``seq`` mesh axis by ``jax.lax.ppermute`` (ICI
    neighbor-to-neighbor traffic only) and a streaming log-sum-exp softmax,
    so no device ever materializes the full (T, T) score matrix or the full
    K/V. Memory per device: O(T/n * T/n) scores, O(T/n) K/V.
  - :func:`ulysses_attention` — all-to-all context parallelism: heads are
    exchanged for sequence shards (``jax.lax.all_to_all``), each device runs
    dense attention for H/n heads over the FULL sequence, then the layout is
    swapped back. One collective pair per attention call; best when
    n_devices <= n_heads and T*T/n scores fit.
  - :func:`blockwise_attention` — the INTRA-device path: the same streaming
    log-sum-exp recurrence over K/V blocks on one device (FlashAttention at
    the XLA level). Under a causal or a segment mask the queries are tiled
    and each tile's loop runs only the key blocks the mask can keep a pair
    of (:func:`block_bounds`, computed on the device from the ids it is
    given; the host counts the same table as ``attention.blocks``):
    O(H * Q_TILE * block_size) score memory. Unmasked it is one scan over
    the blocks, O(T * block_size). Compose with ring/Ulysses when a single
    shard's sequence is itself too long to score densely.

The sharded pair are written as shard_map bodies (take ``axis_name``) plus
convenience wrappers that build the shard_map over a 1-D ``seq`` mesh. All
support the causal mask (global positions reconstructed from the device
index, so the mask is exact across shards); all share one streaming-softmax
fold (:func:`_softmax_fold`). Numerics are validated against dense softmax
attention on the 8-device CPU mesh in tests/test_sequence_parallel.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def dense_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = False,
                    scale: Optional[float] = None) -> jnp.ndarray:
    """Reference single-device attention. (B, T, H, D) -> (B, T, H, D)."""
    d = q.shape[-1]
    scale = (d ** -0.5) if scale is None else scale
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), tk - tq)
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _fold_init(b, h, t, d):
    """Fresh streaming-softmax accumulator (o, m, l), f32; ``d`` is the
    value head's width."""
    return (jnp.zeros((b, h, t, d), jnp.float32),
            jnp.full((b, h, t, 1), -jnp.inf, jnp.float32),
            jnp.zeros((b, h, t, 1), jnp.float32))


def _fold_finalize(o, l, dtype):
    """Normalize + (B, H, T, D) -> (B, T, H, D) in the caller's dtype."""
    out = o / jnp.maximum(l, 1e-30)
    return jnp.einsum("bhqd->bqhd", out).astype(dtype)


def _softmax_fold(q, acc, ck, cv, scale, valid):
    """Fold one K/V block into the streaming-softmax accumulator
    ``(o, m, l)`` — unnormalized output, running max, normalizer. ``valid``
    is an optional (tq, tk) bool mask (causal and/or padding) that sets a
    masked score to -inf. Two guards per row keep a row with no valid key
    yet finite: ``m_safe`` shifts by 0 where the running max is still
    -inf, and ``alpha`` is 0 where the old one was. The shift is finite,
    so ``exp(-inf - m_safe)`` is exactly 0 and a masked element needs no
    guard of its own: nothing reads the mask back out of the scores.
    Shared by the ring and blockwise paths so the delicate numerics live
    once."""
    o, m, l = acc
    s = jnp.einsum("bqhd,bkhd->bhqk", q, ck,
                   preferred_element_type=jnp.float32) * scale
    if valid is not None:
        s = jnp.where(valid, s, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
    p = jnp.exp(s - m_safe)
    alpha = jnp.where(jnp.isinf(m), 0.0, jnp.exp(m - m_safe))
    o = o * alpha + jnp.einsum("bhqk,bkhd->bhqd", p, cv,
                               preferred_element_type=jnp.float32)
    l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    return o, m_new, l


#: keys a block (``block_size``'s default) and queries a tile of
#: :func:`blockwise_attention`; the host counts with the same two
#: (``parallel/packer.py``: ``attention.blocks``)
BLOCK_SIZE = 512
Q_TILE = 512


def block_bounds(segment_ids, t: int, q_tile: int, block_size: int,
                 causal: bool):
    """``(lo, hi)``, each (B, tiles) (B = 1 without ``segment_ids``): the key
    blocks ``lo <= i < hi`` are all that the tile's queries can keep a key
    of. A pair (tile, block) MAY hold a valid (query, key) only if, where
    ``causal``, the block's first key is not after the tile's last query,
    and, where ``segment_ids`` (B, T) is given, the ranges [min, max] of the
    ids of the tile's queries and of the block's keys overlap. ``lo`` is the
    first such block and ``hi`` one past the last (0, 0 where there is none).
    The test is conservative for any ids, sorted or not: a block inside the
    bounds may still hold nothing, a block outside them holds nothing.
    ``numpy`` in, ``numpy`` out (the host counts what the device will run);
    ``jax`` arrays in, ``jax.numpy`` out."""
    xp = jnp if isinstance(segment_ids, jax.Array) else np
    qt, bs = min(q_tile, t), min(block_size, t)
    n_tiles, n_blocks = -(-t // qt), -(-t // bs)
    may = xp.ones((1, n_tiles, n_blocks), bool)
    if causal:
        last_query = xp.minimum((xp.arange(n_tiles) + 1) * qt, t) - 1
        may = may & (xp.arange(n_blocks) * bs <= last_query[:, None])
    if segment_ids is not None:
        def ranges(size, n):
            # the last real id stands in the pad: no range moves
            ids = xp.pad(segment_ids, ((0, 0), (0, n * size - t)),
                         mode="edge").reshape(-1, n, size)
            return ids.min(-1), ids.max(-1)

        q_min, q_max = ranges(qt, n_tiles)
        k_min, k_max = ranges(bs, n_blocks)
        may = (may & (q_min[:, :, None] <= k_max[:, None, :])
               & (k_min[:, None, :] <= q_max[:, :, None]))
    some = may.any(-1)
    return (xp.where(some, may.argmax(-1), 0),
            xp.where(some, n_blocks - may[..., ::-1].argmax(-1), 0))


def blocks_run(segment_ids, causal: bool = True):
    """``(kept, total)``: the (query tile, key block) pairs a call with these
    ``segment_ids`` (B, T), the module's tile and the default block folds,
    and all the rows have. On the host (``numpy`` ids) it is the counter
    ``attention.blocks``."""
    t = segment_ids.shape[-1]
    lo, hi = block_bounds(segment_ids, t, Q_TILE, BLOCK_SIZE, causal)
    return int((hi - lo).sum()), lo.size * -(-t // min(BLOCK_SIZE, t))


def stated_tile(heads: int, t: int) -> dict:
    """What one step of :func:`blockwise_attention`'s masked loop scores,
    for ``heads`` heads over rows of ``t`` tokens: ``q_tile`` queries
    against ``block_size`` keys, and the float32 score tile they fill, in
    MB (``heads * q_tile * block_size * 4`` bytes; an ``attention`` event)."""
    qt, bs = min(Q_TILE, t), min(BLOCK_SIZE, t)
    return {"q_tile": qt, "block_size": bs, "heads": heads,
            "score_tile_mb": round(heads * qt * bs * 4 / 1e6, 2)}


def blockwise_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        block_size: int = BLOCK_SIZE, causal: bool = False,
                        scale: Optional[float] = None,
                        segment_ids: Optional[jnp.ndarray] = None
                        ) -> jnp.ndarray:
    """Single-device memory-efficient attention: ``q`` / ``k`` (B, T, H, D)
    and ``v`` (B, T, H, Dv) -> (B, T, H, Dv). The value head may be narrower
    than the query/key head (deepseek_v2's latent attention: 192 and 128);
    the accumulator is as wide as ``v``.

    The intra-device complement of :func:`ring_attention`: the same
    streaming log-sum-exp softmax over K/V blocks of ``block_size`` keys,
    folded in ascending order: the FlashAttention recurrence expressed at
    the XLA level. T need not divide ``block_size`` (keys pad with a mask).
    ``segment_ids`` (B, T) packs several documents into a row: a query
    attends only to keys of its own segment.

    What the mask can throw away whole is not computed. Where ``causal`` or
    ``segment_ids`` is given, a ``lax.scan`` runs over (row, tile of
    ``Q_TILE`` queries) and, inside, a ``lax.fori_loop`` over the key blocks
    ``lo <= i < hi`` of :func:`block_bounds`, computed on the device from
    the ids the call was given: a 16,384-token causal document folds 528 of
    its 1,024 (512 x 512) pairs. Every element is still masked as before,
    so the bounds decide cost and never the result: a block outside them is
    one whose fold is the identity (``s = -inf``, ``p = 0``, ``alpha = 1``).
    A tile carries its own float32 ``(o, m, l)``; peak score memory is
    O(H * Q_TILE * block_size) (:func:`stated_tile`). The key and value
    blocks pass an optimization barrier before the scan, so they are made
    once a call and not once a tile. The loop is a ``while`` on the device:
    nothing differentiates through it. With no mask at all nothing can be
    skipped: one ``lax.scan`` over the K/V blocks scores every query
    against each, O(T * block_size) scores.
    """
    b, t, h, d = q.shape
    dv = v.shape[-1]
    scale = (d ** -0.5) if scale is None else scale
    bs = min(block_size, t)
    n_blocks = -(-t // bs)
    pad = n_blocks * bs - t
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    if not causal and segment_ids is None:
        return _every_block(q, kp, vp, bs, scale)

    qt = min(Q_TILE, t)
    n_tiles = -(-t // qt)
    q_pad = n_tiles * qt - t
    lo, hi = (jnp.broadcast_to(x, (b, n_tiles)).reshape(-1).astype(jnp.int32)
              for x in block_bounds(segment_ids, t, Q_TILE, block_size,
                                    causal))
    # rows and tiles along one scan axis: a step is one row's tile, so its
    # bounds are that row's own; a row's key blocks are indexed beside it
    tiles = (jnp.pad(q, ((0, 0), (0, q_pad), (0, 0), (0, 0))
                     ).reshape(b * n_tiles, 1, qt, h, d),
             jnp.repeat(jnp.arange(b), n_tiles),
             jnp.tile(jnp.arange(n_tiles), b), lo, hi)
    # the blocks the loops index are made once, before them: left alone the
    # compiler may sink their producers (grouped-query keys and values
    # repeated to every head, and the copies that lay them out) into the
    # scan, which then rebuilds all of K and V for every query tile
    kb, vb = jax.lax.optimization_barrier(
        (kp.reshape(b * n_blocks, 1, bs, h, d),
         vp.reshape(b * n_blocks, 1, bs, h, dv)))
    if segment_ids is not None:
        # a padded query or key is in no segment
        tiles += (jnp.pad(segment_ids, ((0, 0), (0, q_pad)),
                          constant_values=-1).reshape(b * n_tiles, 1, qt),)
        sb = jnp.pad(segment_ids, ((0, 0), (0, pad)),
                     constant_values=-1).reshape(b * n_blocks, 1, bs)

    def tile_step(_, tile):
        cq, row, j, lo, hi = tile[:5]
        q_pos = j * qt + jnp.arange(qt)

        def fold(i, acc):
            at = row * n_blocks + i
            ck, cv = (jax.lax.dynamic_index_in_dim(x, at, keepdims=False)
                      for x in (kb, vb))
            k_pos = i * bs + jnp.arange(bs)
            valid = k_pos[None, :] < t
            if causal:
                valid = valid & (q_pos[:, None] >= k_pos[None, :])
            if segment_ids is not None:   # (1, 1, qt, bs) over (1, H, qt, bs)
                valid = valid & (
                    tile[5][:, None, :, None]
                    == jax.lax.dynamic_index_in_dim(
                        sb, at, keepdims=False)[:, None, None, :])
            return _softmax_fold(cq, acc, ck, cv, scale, valid)

        o, _, l = jax.lax.fori_loop(lo, hi, fold, _fold_init(1, h, qt, dv))
        return None, _fold_finalize(o, l, q.dtype)

    _, out = jax.lax.scan(tile_step, None, tiles)
    return out.reshape(b, n_tiles * qt, h, dv)[:, :t]


def _every_block(q, kp, vp, bs, scale):
    """:func:`blockwise_attention` with nothing to skip: one scan over the
    K/V blocks of ``bs`` keys, all T queries against each; ``kp`` / ``vp``
    are padded to whole blocks."""
    b, t, h, d = q.shape
    dv = vp.shape[-1]
    n_blocks = kp.shape[1] // bs
    # (n_blocks, B, bs, H, D) scan sequence
    kb = jnp.moveaxis(kp.reshape(b, n_blocks, bs, h, d), 1, 0)
    vb = jnp.moveaxis(vp.reshape(b, n_blocks, bs, h, dv), 1, 0)

    def step(acc, blk):
        o, m, l, i = acc
        ck, cv = blk
        k_pos = i * bs + jnp.arange(bs)
        valid = k_pos[None, :] < t
        o, m, l = _softmax_fold(q, (o, m, l), ck, cv, scale, valid)
        return (o, m, l, i + 1), None

    o0, m0, l0 = _fold_init(b, h, t, dv)
    (o, _, l, _), _ = jax.lax.scan(step, (o0, m0, l0, 0), (kb, vb))
    return _fold_finalize(o, l, q.dtype)


def ring_attention_sharded(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           axis_name: str, causal: bool = False,
                           scale: Optional[float] = None) -> jnp.ndarray:
    """shard_map body: q/k/v are the LOCAL (B, T/n, H, D) sequence shards.

    lax.scan over n ring steps; each step attends the local queries to the
    currently-held K/V shard (with exact global-position causal masking),
    folds the block into the streaming-softmax accumulator (running max m,
    normalizer l, unnormalized output o), then rotates the K/V shard to the
    next device with ppermute. The ppermute is inside the scanned step, so
    XLA overlaps the ICI transfer of step i+1's shard with step i's compute.
    """
    n = jax.lax.psum(1, axis_name)
    me = jax.lax.axis_index(axis_name)
    b, t_local, h, d = q.shape
    scale = (d ** -0.5) if scale is None else scale
    q_pos = me * t_local + jnp.arange(t_local)  # global query positions

    def fold(acc, ck, cv, src):
        """Fold the K/V shard currently held (originally device ``src``)."""
        valid = None
        if causal:
            k_pos = src * t_local + jnp.arange(t_local)
            valid = q_pos[:, None] >= k_pos[None, :]
        return _softmax_fold(q, acc, ck, cv, scale, valid)

    o0, m0, l0 = _fold_init(b, h, t_local, d)
    # the accumulators become device-varying after one scan step; the
    # replicated initializers must be cast so the carry types are stable
    o0, m0, l0 = (jax.lax.pcast(x, (axis_name,), to="varying")
                  for x in (o0, m0, l0))
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, i):
        o, m, l, ck, cv = carry
        o, m, l = fold((o, m, l), ck, cv, src=(me - i) % n)
        ck = jax.lax.ppermute(ck, axis_name, perm)
        cv = jax.lax.ppermute(cv, axis_name, perm)
        return (o, m, l, ck, cv), None

    # n-1 scanned fold+rotate steps, then the last held block is folded
    # outside the scan — the final rotation (whose result nobody reads)
    # would otherwise cost a full extra K+V ICI transfer per call
    (o, m, l, ck, cv), _ = jax.lax.scan(
        step, (o0, m0, l0, k, v), jnp.arange(n - 1))
    o, _, l = fold((o, m, l), ck, cv, src=(me - (n - 1)) % n)
    return _fold_finalize(o, l, q.dtype)


def ulysses_attention_sharded(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                              axis_name: str, causal: bool = False,
                              scale: Optional[float] = None) -> jnp.ndarray:
    """shard_map body: all-to-all heads<->sequence swap, dense attention on
    H/n heads x full T, swap back. Requires H % n == 0."""
    # (B, T/n, H, D) -> (B, T, H/n, D)
    qg = jax.lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1,
                            tiled=True)
    kg = jax.lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1,
                            tiled=True)
    vg = jax.lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1,
                            tiled=True)
    out = dense_attention(qg, kg, vg, causal=causal, scale=scale)
    return jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)


def _seq_mesh(mesh: Optional[Mesh], axis: str) -> Mesh:
    if mesh is not None:
        return mesh
    devs = np.array(jax.devices())
    return Mesh(devs, (axis,))


_BODIES = {"ring": ring_attention_sharded, "ulysses": ulysses_attention_sharded}


@functools.lru_cache(maxsize=None)
def _sharded_fn(kind: str, mesh: Mesh, axis: str, causal: bool,
                scale: Optional[float]):
    """Jitted shard_map per (kind, mesh, axis, causal, scale) — cached so
    repeated calls (one per transformer layer per step) hit the jit cache
    instead of retracing a fresh function object every time."""
    body = functools.partial(_BODIES[kind], axis_name=axis, causal=causal,
                             scale=scale)
    spec = P(None, axis, None, None)
    return jax.jit(shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec))


def _sharded_call(kind: str, mesh: Mesh, axis: str, causal: bool,
                  scale: Optional[float], q, k, v):
    sh = NamedSharding(mesh, P(None, axis, None, None))
    fn = _sharded_fn(kind, mesh, axis, causal, scale)
    # device_put is a no-op when the operand already has this sharding
    return fn(jax.device_put(q, sh), jax.device_put(k, sh),
              jax.device_put(v, sh))


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   mesh: Optional[Mesh] = None, axis: str = "seq",
                   causal: bool = False,
                   scale: Optional[float] = None) -> jnp.ndarray:
    """Global-shape entry point: shards (B, T, H, D) over ``axis`` and runs
    :func:`ring_attention_sharded`. T must divide by the mesh size."""
    return _sharded_call("ring", _seq_mesh(mesh, axis), axis, causal, scale,
                         q, k, v)


def ulysses_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      mesh: Optional[Mesh] = None, axis: str = "seq",
                      causal: bool = False,
                      scale: Optional[float] = None) -> jnp.ndarray:
    """Global-shape entry point for the all-to-all path. T and H must divide
    by the mesh size."""
    return _sharded_call("ulysses", _seq_mesh(mesh, axis), axis, causal,
                         scale, q, k, v)
