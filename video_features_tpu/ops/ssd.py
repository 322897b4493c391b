"""The state-space recurrence of a Mamba-2 layer over packed rows.

Per head, with ``a < 0`` and a step ``dt_t > 0``::

    S_t = exp(dt_t * a) * S_{t-1} + dt_t * B_t (x) x_t       S: (P, N)
    y_t = C_t . S_t

over the tokens ``t`` of a row that holds several documents one after the
other: ``S`` is zero before a segment's first token, so nothing crosses from
one document into the next. :func:`ssd_scan` computes it in chunks (the
"state-space duality" form of Dao & Gu 2024): inside a chunk the recurrence
unrolls into one masked (Q, Q) matrix per head that multiplies the chunk's
inputs, between chunks only the (P, N) state is carried. The result does not
depend on the chunk length. ``B``/``C`` come in ``G`` groups, each shared
by ``H / G`` consecutive heads: head ``h`` reads group ``h // (H / G)``
(nemotron_h's ``n_groups`` 8); one group given as (B, T, N) serves every
head (granite's ``mamba_n_groups`` 1).

:func:`causal_conv1d` is the depthwise convolution in front of it, whose
taps do not read across a segment boundary either; lfm2_moe's short
convolution runs the same taps with no bias.

Segments are contiguous runs of one id in a row (``parallel/packer.py
SegmentPacker`` lays them out so); that is what lets "no boundary between
``s`` and ``t``" be tested as ``seg[s] == seg[t]``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


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


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
             b: jnp.ndarray, c: jnp.ndarray, seg: jnp.ndarray,
             chunk: int, state_dtype=jnp.float32) -> jnp.ndarray:
    """``y`` (B, T, H, P) float32 of the recurrence above.

    ``x`` (B, T, H, P) in the compute type, ``dt`` (B, T, H) float32 after
    its softplus, ``a`` (H,) float32 negative, ``b`` / ``c`` (B, T, N) for
    one group or (B, T, G, N) for ``G`` groups, ``seg`` (B, T) int32. ``T``
    need not be a multiple of ``chunk``: the tail is padded with a segment
    of its own. Decays and the carried state are float32 (``state_dtype``:
    the tests carry it in bfloat16 to show that the comparison notices);
    the matrix products take their operands in ``x.dtype`` and accumulate
    in float32.

    With groups the heads run as (G, H / G): every (Q, Q) score matrix is
    computed once a group and serves the group's heads.
    """
    bsz, t, h, p = x.shape
    n = b.shape[-1]
    grouped = b.ndim == 4
    # the head axes of every einsum below, and the group axis of B and C
    g, hs = ("g", "gj") if grouped else ("", "h")
    heads = (b.shape[2], h // b.shape[2]) if grouped else (h,)
    lift = (None,) * len(heads)     # a (B, C, Q) mask over the head axes
    q = int(chunk)
    pad = (-t) % q
    if pad:
        def tail(v):
            return ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2)
        x, dt, b, c = (jnp.pad(v, tail(v)) for v in (x, dt, b, c))
        seg = jnp.pad(seg, ((0, 0), (0, pad)), constant_values=-1)
    nc = (t + pad) // q
    mm = x.dtype
    xc = x.reshape(bsz, nc, q, *heads, p)
    dtc = dt.reshape(bsz, nc, q, *heads).astype(jnp.float32)
    bc = b.reshape(bsz, nc, q, *b.shape[2:]).astype(mm)
    cc = c.reshape(bsz, nc, q, *c.shape[2:]).astype(mm)
    segc = seg.reshape(bsz, nc, q)

    # cum[t] = sum of dt*a over the chunk's tokens up to and including t
    cum = jnp.cumsum(dtc * a.reshape(heads).astype(jnp.float32),
                     axis=2)                                  # (B, C, Q, H)
    xdt = (xc.astype(jnp.float32) * dtc[..., None]).astype(mm)

    # -- inside a chunk: y[t] += sum_{s<=t, same segment} (C_t.B_s)
    #    exp(cum[t] - cum[s]) dt_s x_s; the (Q, Q) scores serve every head
    #    of their group
    causal = jnp.tril(jnp.ones((q, q), bool))
    same = (segc[:, :, :, None] == segc[:, :, None, :]) & causal
    scores = jnp.einsum(f"bct{g}n,bcs{g}n->bc{g}ts", cc, bc,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(same[:, :, None] if grouped else same, scores, 0.0)
    cum_h = jnp.moveaxis(cum, 2, -1)                          # (B, C, H, Q)
    # above the diagonal the difference is positive: held at 0, where the
    # masked score already makes the entry zero, so nothing overflows
    decay = jnp.exp(jnp.minimum(
        cum_h[..., :, None] - cum_h[..., None, :], 0.0))      # (B,C,H,Q,Q)
    mixing = (jnp.expand_dims(scores, scores.ndim - 2) * decay).astype(mm)
    y = jnp.einsum(f"bc{hs}ts,bcs{hs}p->bct{hs}p", mixing, xdt,
                   preferred_element_type=jnp.float32)

    # -- each chunk's own contribution to the state at its end
    last_seg = segc[:, :, -1]                                 # (B, C)
    to_end = jnp.exp(cum[:, :, -1:] - cum)                    # (B, C, Q, H)
    to_end = jnp.where((segc == last_seg[..., None])[(..., *lift)], to_end,
                       0.0)
    weighted = (xdt.astype(jnp.float32) * to_end[..., None]).astype(mm)
    local = jnp.einsum(f"bcs{hs}p,bcs{g}n->bc{hs}pn", weighted, bc,
                       preferred_element_type=jnp.float32)

    # -- between chunks: the state that enters chunk c is that of the
    #    segment the previous chunk ended in, and survives this chunk only
    #    if this chunk ends in the same segment
    prev_seg = jnp.concatenate(
        [jnp.full((bsz, 1), -2, segc.dtype), last_seg[:, :-1]], axis=1)
    keep = jnp.where((last_seg == prev_seg)[(..., *lift)],
                     jnp.exp(cum[:, :, -1]), 0.0)             # (B, C, H)

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
    from_start = jnp.where((segc == prev_seg[..., None])[(..., *lift)],
                           jnp.exp(cum), 0.0)                 # (B, C, Q, H)
    carried = jnp.einsum(f"bct{g}n,bc{hs}pn->bct{hs}p", cc,
                         entering.astype(mm),
                         preferred_element_type=jnp.float32)
    y = y + carried * from_start[..., None]
    return y.reshape(bsz, nc * q, h, p)[:, :t]
