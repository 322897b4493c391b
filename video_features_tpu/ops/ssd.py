"""The state-space recurrence of a Mamba-2 layer over packed rows.

Per head, with ``a < 0`` and a step ``dt_t > 0``::

    S_t = exp(dt_t * a) * S_{t-1} + dt_t * B_t (x) x_t       S: (P, N)
    y_t = C_t . S_t

over the tokens ``t`` of a row that holds several documents one after the
other: ``S`` is zero before a segment's first token, so nothing crosses from
one document into the next. :func:`ssd_chunks` computes it in chunks (the
"state-space duality" form of Dao & Gu 2024): inside a chunk the recurrence
unrolls into one masked (Q, Q) matrix per head that multiplies the chunk's
inputs, between chunks only the (P, N) state is carried. The result does not
depend on the chunk length. ``B``/``C`` come in ``G`` groups, each shared
by ``H / G`` consecutive heads: head ``h`` reads group ``h // (H / G)``
(nemotron_h's ``n_groups`` 8); one group given without its axis serves
every head (granite's ``mamba_n_groups`` 1).

The layout. :func:`ssd_chunks` takes and returns its activations with the
chunk axis ``C`` in front and a chunk's ``Q`` tokens last, (B, C, channels,
Q), and every product in it keeps that order: the batch axes (B, C, heads)
before the two a product multiplies, the tokens on the last axis. A TPU
product writes its batch axes outermost, so this is the one layout in which
what each product writes is what the next one reads; the Mamba mixers
(``models/granite_hybrid.py``, ``models/nemotron_h.py``) project into it
and out of it. With token-major (B, T, H, P) activations the compiler
relays them for the products and back after them: three to five float32
copies of 537 MB a layer at the cells' widths. :func:`ssd_scan` is the same
scan for token-major arrays.
To see it without a chip, compile for a described v5e and read the entry
computation's ``copy`` operations (``tests/test_kernels_v5e_compile.py``).

:func:`causal_conv1d` is the depthwise convolution in front of it, whose
taps do not read across a segment boundary either; lfm2_moe's short
convolution runs the same taps with no bias.

Segments are contiguous runs of one id in a row (``parallel/packer.py
SegmentPacker`` lays them out so); that is what lets "no boundary between
``s`` and ``t``" be tested as ``seg[s] == seg[t]``.
"""
from __future__ import annotations

from typing import Mapping, Optional

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint


def causal_conv1d(x: jnp.ndarray, weight: jnp.ndarray,
                  bias: Optional[jnp.ndarray], seg: jnp.ndarray
                  ) -> jnp.ndarray:
    """Depthwise causal convolution along ``T``: ``x`` (B, T, C), ``weight``
    (K, C) with tap ``j`` on ``x[t - (K - 1 - j)]``, ``bias`` (C,) or
    ``None``, ``seg`` (B, T). A tap whose source token lies before the row or
    in another segment reads zero. Accumulates in float32, returns
    ``x.dtype``."""
    k = weight.shape[0]
    t = x.shape[1]
    out = x.astype(jnp.float32) * weight[k - 1].astype(jnp.float32)
    if bias is not None:
        out = bias.astype(jnp.float32) + out
    for back in range(1, k):
        source = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :t]
        source_seg = jnp.pad(seg, ((0, 0), (back, 0)),
                             constant_values=-1)[:, :t]
        tap = jnp.where((source_seg == seg)[..., None],
                        source.astype(jnp.float32), 0.0)
        out = out + tap * weight[k - 1 - back].astype(jnp.float32)
    return out.astype(x.dtype)


def pad_to_chunks(chunk: int, seg: jnp.ndarray, *rows: jnp.ndarray):
    """``seg`` (B, T) and ``rows`` (B, T, ...) padded along ``T`` to a
    multiple of ``chunk``; the padding is a segment of its own (-1), so no
    tap and no state reads across it."""
    pad = (-seg.shape[1]) % chunk
    if pad:
        seg = jnp.pad(seg, ((0, 0), (0, pad)), constant_values=-1)
        rows = tuple(jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                     for v in rows)
    return (seg, *rows)


def chunked(v: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """(B, T, ...) -> (B, T / chunk, ..., chunk): a chunk's tokens on the
    last axis, the layout of :func:`ssd_chunks`."""
    bsz, t = v.shape[:2]
    return jnp.moveaxis(v.reshape(bsz, t // chunk, chunk, *v.shape[2:]), 2,
                        -1)


def mamba_in(w: Mapping[str, jnp.ndarray], u: jnp.ndarray, seg: jnp.ndarray,
             d_inner: int, conv_dim: int, chunk: int):
    """A Mamba-2 mixer's way into the layout of :func:`ssd_chunks`: ``u``
    (B, T, D) padded to whole chunks and projected by ``w["in_proj"]`` into
    ``z`` (B, C, d_inner, Q) float32, the convolved and silu'd ``x B C``
    (B, C, conv_dim, Q) in ``u.dtype`` and ``dt`` (B, C, H, Q) float32
    after its softplus; with ``seg`` as (B, C, Q). ``z`` and ``dt`` are
    projected straight into the layout: written as the product and a
    transpose whose layout is pinned, which the compiler folds into the
    product's output (the CPU backend has no bfloat16 product that writes
    its output transposed). The convolution runs along ``T`` and its output
    is laid out once."""
    seg, u = pad_to_chunks(chunk, seg, u)
    bsz, t = seg.shape
    proj, rows = w["in_proj"], u.reshape(bsz, t // chunk, chunk, -1)

    def into_chunks(weight):
        out = jnp.einsum("bcqd,dk->bcqk", rows, weight,
                         preferred_element_type=jnp.float32)
        return with_layout_constraint(jnp.swapaxes(out, -1, -2),
                                      Layout(tuple(range(out.ndim))))

    z = into_chunks(proj[:, :d_inner])
    dt = jax.nn.softplus(into_chunks(proj[:, d_inner + conv_dim:])
                         + w["dt_bias"].astype(jnp.float32)[:, None])
    xbc = jnp.dot(u, proj[:, d_inner:d_inner + conv_dim],
                  preferred_element_type=jnp.float32).astype(u.dtype)
    xbc = jax.nn.silu(causal_conv1d(xbc, w["conv_w"], w["conv_b"], seg)
                      .astype(jnp.float32)).astype(u.dtype)
    return z, chunked(xbc, chunk), dt, chunked(seg, chunk)


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
             b: jnp.ndarray, c: jnp.ndarray, seg: jnp.ndarray,
             chunk: int, state_dtype=jnp.float32) -> jnp.ndarray:
    """:func:`ssd_chunks` for token-major arrays: ``x`` (B, T, H, P),
    ``dt`` (B, T, H), ``b`` / ``c`` (B, T, N) or (B, T, G, N), ``seg``
    (B, T) -> ``y`` (B, T, H, P) float32. ``T`` need not be a multiple of
    ``chunk``: the tail is padded with a segment of its own."""
    bsz, t, h, p = x.shape
    seg, x, dt, b, c = pad_to_chunks(chunk, seg, x, dt, b, c)
    y = ssd_chunks(*(chunked(v, chunk) for v in (x, dt)), a,
                   *(chunked(v, chunk) for v in (b, c, seg)), state_dtype)
    return jnp.moveaxis(y, -1, 2).reshape(bsz, -1, h, p)[:, :t]


def ssd_chunks(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
               b: jnp.ndarray, c: jnp.ndarray, seg: jnp.ndarray,
               state_dtype=jnp.float32) -> jnp.ndarray:
    """``y`` (B, C, H, P, Q) float32 of the recurrence above, in chunks of
    ``Q`` tokens.

    ``x`` (B, C, H, P, Q) in the compute type, ``dt`` (B, C, H, Q) float32
    after its softplus, ``a`` (H,) float32 negative, ``b`` / ``c``
    (B, C, N, Q) for one group or (B, C, G, N, Q) for ``G`` groups, ``seg``
    (B, C, Q) int32. Decays and the carried state are float32
    (``state_dtype``: the tests carry it in bfloat16 to show that the
    comparison notices); the matrix products take their operands in
    ``x.dtype`` and accumulate in float32. ``dt`` scales the operand that
    is computed beside each product (the (Q, Q) mixing matrix, the decay
    to the chunk's end), so both products read ``x`` as it comes.

    With groups the heads run as (G, H / G): every (Q, Q) score matrix is
    computed once a group and serves the group's heads.
    """
    bsz, nc, h, p, q = x.shape
    n = b.shape[-2]
    grouped = b.ndim == 5
    # the head axes of every einsum below, and the group axis of B and C
    g, hs = ("g", "gj") if grouped else ("", "h")
    heads = (b.shape[2], h // b.shape[2]) if grouped else (h,)
    lift = (None,) * len(heads)     # a (B, C, Q) mask over the head axes
    mm = x.dtype
    xc = x.reshape(bsz, nc, *heads, p, q)
    dtc = dt.reshape(bsz, nc, *heads, q).astype(jnp.float32)
    bc, cc = b.astype(mm), c.astype(mm)

    # cum[t] = sum of dt*a over the chunk's tokens up to and including t
    cum = jnp.cumsum(dtc * a.reshape(*heads, 1).astype(jnp.float32),
                     axis=-1)                                 # (B, C, H, Q)

    # -- inside a chunk: y[t] += sum_{s<=t, same segment} (C_t.B_s)
    #    exp(cum[t] - cum[s]) dt_s x_s; the (Q, Q) scores serve every head
    #    of their group
    causal = jnp.tril(jnp.ones((q, q), bool))
    same = (seg[:, :, :, None] == seg[:, :, None, :]) & causal
    scores = jnp.einsum(f"bc{g}nt,bc{g}ns->bc{g}ts", cc, bc,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(same[:, :, None] if grouped else same, scores, 0.0)
    # above the diagonal the difference is positive: held at 0, where the
    # masked score already makes the entry zero, so nothing overflows
    decay = jnp.exp(jnp.minimum(
        cum[..., :, None] - cum[..., None, :], 0.0))          # (B,C,H,Q,Q)
    mixing = (jnp.expand_dims(scores, scores.ndim - 2) * decay
              * dtc[..., None, :]).astype(mm)
    y = jnp.einsum(f"bc{hs}ts,bc{hs}ps->bc{hs}pt", mixing, xc,
                   preferred_element_type=jnp.float32)

    # -- each chunk's own contribution to the state at its end
    last_seg = seg[:, :, -1]                                  # (B, C)
    to_end = jnp.exp(cum[..., -1:] - cum)                     # (B, C, H, Q)
    to_end = jnp.where((seg == last_seg[..., None])[:, :, *lift], to_end,
                       0.0)
    weighted = (xc.astype(jnp.float32)
                * (to_end * dtc)[..., None, :]).astype(mm)
    local = jnp.einsum(f"bc{hs}ps,bc{g}ns->bc{hs}pn", weighted, bc,
                       preferred_element_type=jnp.float32)

    # -- between chunks: the state that enters chunk c is that of the
    #    segment the previous chunk ended in, and survives this chunk only
    #    if this chunk ends in the same segment
    prev_seg = jnp.concatenate(
        [jnp.full((bsz, 1), -2, seg.dtype), last_seg[:, :-1]], axis=1)
    keep = jnp.where((last_seg == prev_seg)[:, :, *lift],
                     jnp.exp(cum[..., -1]), 0.0)              # (B, C, H)

    def carry_on(state, chunk_c):
        keep_c, local_c = chunk_c
        entering = state
        state = (state.astype(jnp.float32) * keep_c[..., None, None]
                 + local_c).astype(state_dtype)
        return state, entering

    _, entering = jax.lax.scan(
        carry_on, jnp.zeros((bsz, *heads, p, n), state_dtype),
        (jnp.moveaxis(keep, 1, 0), jnp.moveaxis(local, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                   # (B,C,H,P,N)

    # -- y[t] += exp(cum[t]) C_t . S_entering for the tokens still in the
    #    segment the state belongs to
    from_start = jnp.where((seg == prev_seg[..., None])[:, :, *lift],
                           jnp.exp(cum), 0.0)                 # (B, C, H, Q)
    carried = jnp.einsum(f"bc{g}nt,bc{hs}pn->bc{hs}pt", cc,
                         entering.astype(mm),
                         preferred_element_type=jnp.float32)
    # the barrier keeps the decay in the read-out's own product: else the
    # compiler moves the sum into the mixer's next fusion and writes the
    # decay out there, broadcast to a float32 (B, C, H, P, Q)
    y = y + jax.lax.optimization_barrier(carried * from_start[..., None, :])
    return y.reshape(bsz, nc, h, p, q)
