"""RAFT correlation-pyramid lookup, gather-free (one-hot matmul), for TPU.

The reference implements the per-iteration windowed lookup as a
``grid_sample`` bilinear gather over each pyramid level (reference
models/raft/raft_src/corr.py:29-50): 81 taps x 4 bilinear corners per query
pixel — random scalar loads, the classic GPU formulation.

TPU redesign: random gathers are the one access pattern the TPU dislikes, so
the lookup is recast as two dense contractions per level that ride the MXU.
For each query p the 10x10 corner window of ``corr_l[p]`` (10 = 2r+2 corner
rows/cols covering all 81 bilinearly-interpolated taps) equals

    window[p] = Y[p] @ corr_l[p] @ X[p]^T

where ``Y[p]`` (10, Hl) and ``X[p]`` (10, Wl) are one-hot row selectors built
from ``floor``-ed window base coordinates by an iota comparison. Out-of-range
rows have all-zero one-hots, which reproduces the reference's zeros-padding
semantics with no clamping or masking. The four bilinear corner blends then
reduce the (10, 10) corner window to the (9, 9) tap window with scalar
weights per query. Channel order matches the reference quirk (x-offset
slowest; corr.py:37-43 adds its meshgrid "dy" to x).

Four forms with the same values, and one function that picks among them
(:func:`prepare_lookup`, from the backend and the level-0 plane's shape;
no config key, environment variable or caller selects a form):

  - ``gather`` — the reference's formulation (models/raft.py
    corr_lookup_gather): every backend but the TPU, and the parity
    reference of the tests;
  - ``proj`` — :func:`corr_lookup_proj`: ONE Pallas kernel for all levels
    over one plane a query in which the levels sit side by side
    (:func:`place_levels`), fused with the motion encoder's 1x1
    ``convc1``. The TPU's form wherever that plane fits a VMEM tile;
  - ``level`` — :func:`corr_lookup_pallas`: one Pallas kernel a level over
    lane-padded planes. The TPU's form once the one plane is too large
    (a 1600x2048 input) while each level still fits;
  - ``onehot`` — :func:`corr_lookup_onehot`: the formulation above in plain
    XLA, no tiling constraint. The TPU's form past both size gates, and the
    kernels' twin in the tests and on the chip (chip_smoke.py stage 5:
    both kernels within 1e-4 of it at (30, 40), (28, 28), (8, 8),
    (55, 128) under the extractors' precision=float32 matmul-precision
    pin; under bfloat16 the contraction drifts ~8e-3, that mode's
    contract).

What bounds ``proj``, measured on a v5e at 240x320 (153,600 queries a
call, 128 pairs; PERF.md section 6, PR 31). With every level under a
128-lane pad of its own the plane was 64 x 128 cells, 32,768 B a query of
which 19% held data, and the kernel took 53.7 ns a query: 610 GB/s, three
quarters of the chip's 819, so no change to its selectors could gain more
than a quarter. With the levels on one shelf (32 x 128 cells, 16,384 B,
39% data) and the same contractions it took 47.8 ns, 342 GB/s: now
bound by per-query vector work, most of it moving each level's 9 x 9 taps
from the query's tile into the projection's rows (36 row gathers a
query), and by spills of the unrolled tile. Sharing the contractions
among a shelf's levels (9 row gathers, 7 selector tiles for 16) took it
to 30.0 ns, and one coordinate block for two to 24.8 ns: 662 GB/s, at the
memory wall of the new layout. A plane that is mostly level 0 (a
436x1024 input: 82% data) was at that wall before and gains the bytes it
sheds, no more.

A lane-dense packing of the pyramid (several image rows a 128-lane line,
5.8x fewer bytes an iteration) was built in round 3 and lost to the padded
planes: every selector needed row-in-line arithmetic, at twice today's
selection cost. Commit da49f76 is the last that holds it. This layout
keeps one image row a lane line.
"""
from __future__ import annotations

import functools
import itertools
from typing import Any, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _blend(window: jnp.ndarray, fx: jnp.ndarray, fy: jnp.ndarray,
           n: int) -> jnp.ndarray:
    """(..., 2r+2, 2r+2) corner windows -> (..., n*n) taps, x-offset slowest.

    window[..., yy, xx] = corr at (iy+yy, ix+xx); fx, fy broadcast over the
    window dims."""
    fx = fx[..., None, None]
    fy = fy[..., None, None]
    v = ((1 - fy) * (1 - fx) * window[..., :n, :n]
         + (1 - fy) * fx * window[..., :n, 1:]
         + fy * (1 - fx) * window[..., 1:, :n]
         + fy * fx * window[..., 1:, 1:])
    # tap channel k = xx*n + yy  (the reference's x-slowest order)
    v = jnp.swapaxes(v, -1, -2)
    return v.reshape(*v.shape[:-2], n * n)


def corr_lookup_onehot(pyramid: Sequence[jnp.ndarray], coords: jnp.ndarray,
                       radius: int = 4) -> jnp.ndarray:
    """Pure-XLA twin of the fused kernels. pyramid: per level (B, P, Hl, Wl);
    coords: (B, H, W, 2) level-0 (x, y). Returns (B, H, W, L*(2r+1)^2)."""
    b, h, w, _ = coords.shape
    p = h * w
    n = 2 * radius + 1
    d10 = jnp.arange(n + 1, dtype=jnp.float32)
    cx = coords[..., 0].reshape(b, p)
    cy = coords[..., 1].reshape(b, p)
    out = []
    for lvl, corr in enumerate(pyramid):
        hl, wl = corr.shape[2], corr.shape[3]
        px0 = cx / (2 ** lvl) - radius
        py0 = cy / (2 ** lvl) - radius
        ix = jnp.floor(px0)
        iy = jnp.floor(py0)
        ycorn = iy[..., None] + d10  # (B, P, 10)
        xcorn = ix[..., None] + d10
        ysel = (ycorn[..., None] ==
                jnp.arange(hl, dtype=jnp.float32)).astype(corr.dtype)
        xsel = (xcorn[..., None] ==
                jnp.arange(wl, dtype=jnp.float32)).astype(corr.dtype)
        t = jnp.einsum("bpyh,bphw->bpyw", ysel, corr)
        window = jnp.einsum("bpyw,bpxw->bpyx", t, xsel)
        out.append(_blend(window, px0 - ix, py0 - iy, n))
    return jnp.concatenate(out, axis=-1).reshape(b, h, w, -1)


# ---- per-level fused kernel over lane-padded planes (TPU default) --------

def _level_kernel(px0_ref, py0_ref, corr_ref, out_ref, *, radius: int):
    """Block shapes: px0/py0 (1, TP, 1, 1) — pre-expanded on the host so no
    rank-changing relayout happens in-kernel (Mosaic rejects 1D->3D
    reshapes); corr (1, TP, Hl, Wl); out (1, TP, n*n) with tap channel
    k = xx*n + yy (x-offset slowest — the reference's order). The flatten
    happens IN-kernel as a lane concat of the n sublane rows: emitting
    (TP, n, n) and reshaping on the host instead costs a full extra HBM
    pass per level per GRU iteration (measured ~43 ms per 64-pair RAFT
    forward, re-laying (9,9)-minor tiles into dense lanes)."""
    n = 2 * radius + 1
    tp, hl, wl = corr_ref.shape[1:]
    px0 = px0_ref[0]  # (TP, 1, 1)
    py0 = py0_ref[0]
    ix = jnp.floor(px0)
    iy = jnp.floor(py0)
    # Mosaic iota is integer-only; compare in f32 (floor() values are exact)
    d10 = jax.lax.broadcasted_iota(
        jnp.int32, (1, n + 1, 1), 1).astype(jnp.float32)
    ysel = (iy + d10 ==
            jax.lax.broadcasted_iota(
                jnp.int32, (tp, n + 1, hl), 2).astype(jnp.float32)
            ).astype(jnp.float32)
    xsel = (ix + d10 ==
            jax.lax.broadcasted_iota(
                jnp.int32, (tp, n + 1, wl), 2).astype(jnp.float32)
            ).astype(jnp.float32)
    corrv = corr_ref[0].astype(jnp.float32)  # (TP, Hl, Wl)
    # contract x first, then y, so the window lands as [p, xx, yy]
    u = jax.lax.dot_general(                 # (TP, 10x, Hl)
        xsel, corrv, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    window = jax.lax.dot_general(            # (TP, 10x, 10y)
        u, ysel, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    fx = px0 - ix  # (TP, 1, 1), broadcasts over the window dims
    fy = py0 - iy
    blended = ((1 - fx) * (1 - fy) * window[:, :n, :n]
               + fx * (1 - fy) * window[:, 1:, :n]
               + (1 - fx) * fy * window[:, :n, 1:]
               + fx * fy * window[:, 1:, 1:])  # (TP, n_x, n_y)
    for i in range(n):  # static lane-sliced stores: row i -> taps [i*n, i*n+n)
        out_ref[0, :, i * n:(i + 1) * n] = blended[:, i, :]


def align_level(corr: jnp.ndarray) -> jnp.ndarray:
    """Zero-pad a (B, P, Hl, Wl) level so Hl is an 8-sublane and Wl a
    128-lane multiple — the physical tiling Mosaic wants for the kernel's
    VMEM blocks. Zero padding is semantically free for the lookup: a window
    corner landing in the pad region one-hot-selects a zero, which IS the
    reference's zeros-padding rule (corr.py bilinear_sampler zeros mode).

    Callers running the lookup inside a scan (RAFT's 20-iteration GRU)
    should align the loop-invariant pyramid ONCE before the scan — XLA does
    not hoist the pads out of the while body, and paying them per iteration
    measured ~30% of the whole RAFT forward."""
    _, _, hl, wl = corr.shape
    hlp = -(-hl // 8) * 8
    wlp = -(-wl // 128) * 128
    if (hlp, wlp) == (hl, wl):
        return corr
    return jnp.pad(corr, ((0, 0), (0, 0), (0, hlp - hl), (0, wlp - wl)))


def _best_tile(p: int, cap: int) -> int:
    """Largest divisor of p that is <= cap and usable as a block's
    second-minor dim (multiple of 8, or the whole array, per the Pallas TPU
    block rule); a dividing tile means no P padding of the coords and no
    output slice — both of which would otherwise run EVERY scan iteration
    (for RAFT's 224px geometry, P=784 with tile 128 re-padded to 896 and
    re-sliced 20 times per forward). Falls back to an 8-aligned cap (pad
    path) when p has no usable divisor >= 32."""
    for t in range(min(cap, p), 0, -1):
        if p % t == 0 and (t % 8 == 0 or t == p) and t >= 32:
            return t
    return max(8, (min(cap, p) // 8) * 8)


#: VMEM budget for one corr block (leaves room for Mosaic's double
#: buffering + the selector/accumulator tensors). Sizing the tile to fill
#: this matters: with tiles capped at 128 queries the grid ran 448 programs
#: per level and ALL levels cost the same ~25 ms/forward — pure
#: per-program overhead, not compute or DMA.
_VMEM_BLOCK_BYTES = 2 * 1024 * 1024  # corr-block bytes; hardware-probed on
#                                      v5e: 4 MiB blocks compile standalone
#                                      but overflow INSIDE the jitted RAFT
#                                      scan (VMEM is shared with the
#                                      surrounding program), 2 MiB fits
_MAX_TILE_P = 256


def pallas_lookup_supported(pyramid: Sequence[jnp.ndarray]) -> bool:
    """Whether the per-level kernel can tile these planes within the probed
    VMEM envelope: even an 8-query tile must fit the budget, which holds up
    to 65,536 aligned cells a plane (a 2160x3840 input's 272x512 level-0
    plane does not). :func:`prepare_lookup` then hands the raw levels to
    :func:`corr_lookup_onehot`, the tiling-free twin."""
    for c in pyramid:
        hl, wl = c.shape[2], c.shape[3]
        plane = (-(-hl // 8) * 8) * (-(-wl // 128) * 128) * 4
        if 8 * plane > _VMEM_BLOCK_BYTES:
            return False
    return True


@functools.partial(jax.jit,
                   static_argnames=("radius", "interpret", "tile_p"))
def corr_lookup_level_pallas(corr: jnp.ndarray, px0: jnp.ndarray,
                             py0: jnp.ndarray, radius: int = 4,
                             interpret: bool = False,
                             tile_p: Optional[int] = None) -> jnp.ndarray:
    """One pyramid level: corr (B, P, Hl, Wl), window base coords px0/py0
    (B, P) (level coords minus radius). Returns (B, P, (2r+1)^2)."""
    corr = align_level(corr)  # no-op when the caller pre-aligned
    b, p, hl, wl = corr.shape
    n = 2 * radius + 1
    if hl == 0 or wl == 0:
        # degenerate level (tiny inputs pool to 0x0): every tap reads the
        # zeros-padding region
        return jnp.zeros((b, p, n * n), jnp.float32)
    if tile_p is None:
        # as many queries per program as the VMEM budget allows: fewer,
        # bigger programs matter because the coarse levels are
        # per-program-latency-bound, not compute-bound. The floor of 8
        # keeps the tile a legal sublane multiple; oversized planes where
        # even that floor would bust the budget are refused loudly
        # (pallas_lookup_supported is the caller-facing check).
        if 8 * hl * wl * 4 > _VMEM_BLOCK_BYTES:
            raise ValueError(
                f"corr plane ({hl}x{wl}) too large for any legal VMEM "
                "tile; use corr_lookup_onehot (pallas_lookup_supported "
                "gates this dispatch)")
        tile_p = min(_MAX_TILE_P,
                     max(8, _VMEM_BLOCK_BYTES // (hl * wl * 4)))
    tp = _best_tile(p, tile_p)
    pp = -(-p // tp) * tp
    if pp != p:
        corr = jnp.pad(corr, ((0, 0), (0, pp - p), (0, 0), (0, 0)))
        px0 = jnp.pad(px0, ((0, 0), (0, pp - p)))
        py0 = jnp.pad(py0, ((0, 0), (0, pp - p)))
    coord_spec = pl.BlockSpec((1, tp, 1, 1), lambda bi, pi: (bi, pi, 0, 0),
                              memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_level_kernel, radius=radius),
        name="corr_lookup_level",  # the kernel's own name in a device trace
        grid=(b, pp // tp),
        in_specs=[
            coord_spec,
            coord_spec,
            pl.BlockSpec((1, tp, hl, wl), lambda bi, pi: (bi, pi, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, tp, n * n), lambda bi, pi: (bi, pi, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, pp, n * n), jnp.float32),
        # grid iterations are independent (each owns its query tile):
        # declaring them parallel lets Mosaic pipeline the block DMAs more
        # aggressively (the coarse levels are DMA-latency-bound)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(px0.astype(jnp.float32)[..., None, None],
      py0.astype(jnp.float32)[..., None, None], corr)
    return out[:, :p]


def corr_lookup_pallas(pyramid: Sequence[jnp.ndarray], coords: jnp.ndarray,
                       radius: int = 4,
                       interpret: bool = False) -> jnp.ndarray:
    """Full 4-level lookup via the fused per-level kernel; same signature
    and channel layout as :func:`corr_lookup_onehot`.

    The pair-batch dim folds into the query dim before the kernel: the
    lookup is purely per-query, so (B, P) queries are just B*P queries —
    one flat grid instead of a (B, P/tile) one. The coarse levels are
    per-program-latency-bound (tiny DMAs), so halving the program count
    measurably shortens the RAFT scan."""
    b, h, w, _ = coords.shape
    p = h * w
    cx = coords[..., 0].reshape(1, b * p)
    cy = coords[..., 1].reshape(1, b * p)
    out: List[jnp.ndarray] = []
    for lvl, corr in enumerate(pyramid):
        px0 = cx / (2 ** lvl) - radius
        py0 = cy / (2 ** lvl) - radius
        flat = corr.reshape(1, b * p, *corr.shape[2:])
        out.append(corr_lookup_level_pallas(flat, px0, py0, radius,
                                            interpret=interpret))
    return jnp.concatenate(out, axis=-1).reshape(b, h, w, -1)


# ---- fused lookup + convc1 projection (round-4 TPU default) --------------
#
# Round-4 profiling: the four per-level lookup kernels cost ~100 ms of a
# 215 ms I3D RGB+Flow step and ALL levels cost the same ~25 ms despite
# 4-64x different plane sizes — each level paid for a 128-lane-padded width
# of its own (selector build + blend + 9-lane-wide stores). Downstream, the
# (B, H, W, 324) lookup output is a relayout boundary XLA cannot see through
# (~17 ms/step of reshape passes feeding the motion encoder's convc1,
# models/raft.py BasicMotionEncoder).
#
# This kernel removes both ends at once:
#   - the bilinear blend folds INTO the selectors (9 weighted rows instead
#     of 10 one-hot rows + a 4-corner blend), and
#   - the motion encoder's convc1 (a 1x1 conv, i.e. a (324, 256) matmul)
#     folds INTO the kernel as one projection of the tap windows off a
#     VMEM scratch — so the kernel emits the post-conv (TP, 256)
#     activation (dense, tile-aligned stores) and the 324-channel
#     intermediate never exists.
#
# All four levels ride ONE kernel over ONE pyramid plane a query (one
# contiguous block DMA per grid step). One image row stays one lane line;
# where a level sits in the plane is :func:`place_levels`' shelf rule. The
# projection weight is a constant-index block, so Mosaic keeps it resident
# across grid steps.


class ProjMeta(NamedTuple):
    """Where one level's (rows, width) data cells sit inside the plane."""
    row_off: int   # sublane offset of the level's shelf (a multiple of 8)
    rows: int      # the level's own rows, Hl
    lane_off: int  # lane offset of the level's first column
    width: int     # the level's own columns, Wl


def _up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def place_levels(shapes: Sequence[Tuple[int, int]]
                 ) -> Tuple[Tuple[ProjMeta, ...], Tuple[int, int]]:
    """The plane's geometry, a function of the (Hl, Wl) level shapes alone:
    ``(metas, (rows, lanes))``. The lane width is the 128-multiple that
    holds the widest level. A level goes to the right of the one before it
    while that width has room, else it starts a new shelf below; a shelf is
    as tall as its tallest level, rounded up to 8 sublanes. Levels that
    halve each other's sides fill one shelf when level 0 is at most half
    the lane width ((30, 40), (28, 28): (32, 128), half the cells of one
    128-lane pad a level); a level 0 that fills the width stands alone
    above one shelf of levels 1-3 ((55, 128): 56 + 32 rows); when no two
    levels fit side by side the rule's answer is the plain sublane stack."""
    lanes = max(128, max(_up(wl, 128) for _, wl in shapes))
    metas = []
    row_off = lane = shelf_rows = 0
    for hl, wl in shapes:
        if lane + wl > lanes:  # no room beside the level before
            row_off, lane, shelf_rows = row_off + shelf_rows, 0, 0
        metas.append(ProjMeta(row_off, hl, lane, wl))
        lane += wl
        shelf_rows = max(shelf_rows, _up(hl, 8) if wl else 0)
    return tuple(metas), (row_off + shelf_rows, lanes)


def _shelves(metas: Sequence[ProjMeta]
             ) -> List[Tuple[int, int, List[Tuple[int, ProjMeta]]]]:
    """(row_off, rows, [(level index, meta)]) per shelf, top to bottom;
    levels without a cell (tiny inputs pool to 0x0) are on no shelf."""
    held = [(lvl, m) for lvl, m in enumerate(metas) if m.rows and m.width]
    return [(row_off, max(_up(m.rows, 8) for _, m in shelf), shelf)
            for row_off, shelf in (
                (off, list(group)) for off, group in itertools.groupby(
                    held, key=lambda level: level[1].row_off))]


def stack_aligned_pyramid(pyramid: Sequence[jnp.ndarray]
                          ) -> Tuple[jnp.ndarray, Tuple[ProjMeta, ...]]:
    """Lay the (B, P, Hl, Wl) levels out in ONE zero-filled (B, P, rows,
    lanes) plane as :func:`place_levels` says (the zeros ARE the
    reference's out-of-range rule, see :func:`align_level`, and they keep a
    level's window from reading its neighbour: every cell between and
    below the levels of a shelf is a zero). Hoist this OUT of the GRU scan
    (loop-invariant).

    Every level is padded out to the whole plane and the planes are summed
    (x + 0 is x): on a v5e that is one fusion over the levels and one copy
    into the layout the kernel reads — most of what is left of RAFT's
    pyramid stage since the levels come from pooled feature maps
    (models/raft.py build_corr_pyramid). Concatenating the levels of a
    shelf along the lanes compiles to a 75-lane concatenate and a second
    pass that pads it to 128: 2.24 ms a pair for the whole 240x320 forward
    against this form's 2.17, with 2.2 GB more of temporaries at 128 pairs
    (chip runs, PR 31). Emitting the plane from ONE dot against a
    zero-padded stack of the pooled maps was measured and not kept
    (PR 25): the MXU's result has the query in its rows, so the dot
    writes (B, h, P, w) and an unnamed copy re-tiles it."""
    metas, (rows, lanes) = place_levels([c.shape[2:] for c in pyramid])
    plane = None
    for level, m in zip(pyramid, metas):
        if m.rows == 0 or m.width == 0:
            continue
        cells = jnp.pad(level, (
            (0, 0), (0, 0), (m.row_off, rows - m.row_off - m.rows),
            (m.lane_off, lanes - m.lane_off - m.width)))
        plane = cells if plane is None else plane + cells
    return plane, metas


def stacked_plane_cells(h8: int, w8: int, levels: int = 4) -> int:
    """Per-query cell count (rows * lanes) of the plane
    :func:`stack_aligned_pyramid` builds for a /8 feature grid of
    (h8, w8) — levels floor-halved with the odd-drop rule
    (build_corr_pyramid's torch avg_pool semantics), placed by
    :func:`place_levels`. Shared by the VMEM support gate here and the
    flow-stream HBM budget (extractors/i3d_flow.py _stacks_per_forward) so
    the geometry math has exactly one owner."""
    _, (rows, lanes) = place_levels(
        [(h8 >> lvl, w8 >> lvl) for lvl in range(levels)])
    return rows * lanes


def plane_fill(metas: Sequence[ProjMeta]
               ) -> Tuple[Optional[int], Optional[float]]:
    """(cells a query, share of them that hold data) of the plane these
    placements describe: what the ``corr_lookup`` span event states.
    (None, None) for the forms that have no plane (no placements)."""
    if not metas:
        return None, None
    _, (rows, lanes) = place_levels([(m.rows, m.width) for m in metas])
    cells = rows * lanes
    return cells, sum(m.rows * m.width for m in metas) / cells


def proj_lookup_supported(pyramid: Sequence[jnp.ndarray]) -> bool:
    """Whether the fused projection kernel can tile these planes: one
    plane block at the 8-query tile floor must fit the probed VMEM budget
    (same envelope as the per-level kernel)."""
    h0, w0 = pyramid[0].shape[2], pyramid[0].shape[3]
    cells = stacked_plane_cells(h0, w0, levels=len(pyramid))
    return 8 * cells * 4 <= _VMEM_BLOCK_BYTES


def _proj_kernel(c_ref, corr_ref, w_ref, b_ref, out_ref, taps_ref,
                 *, radius: int, metas: Tuple[ProjMeta, ...]):
    """One grid step: TP queries x ALL levels -> relu(lookup @ W + b).

    Block shapes: c (1, TP, 1, 2), a query's level-0 (x, y) centre in the
    two lanes of a tile of its own — pre-expanded on the host so no
    rank-changing relayout happens in-kernel (Mosaic rejects 1D->3D
    reshapes), and ONE block where cx and cy as (1, TP, 1, 1) blocks each
    cost a relayout fusion and a strided DMA an iteration (4.8% of the
    240x320 forward on a v5e); corr
    (1, TP, rows, lanes) — the plane; w (n*L*n, C) with rows in the
    scratch's order (x-offset xx slowest, then level, then yy); b (1, C);
    out (1, TP, C); taps_ref a (TP, n*L*n) VMEM scratch. The blended
    windows land in scratch via lane-sliced stores (never HBM), then ONE
    rank-2 (TP, n*L*n) @ (n*L*n, C) matmul projects them — Mosaic's
    tpu.matmul takes exactly one contracting dim and position-matched
    batch dims only, so the multi-dim-contraction and batched forms of this
    projection are unavailable (both probed on hardware).

    A shelf's levels share both contractions. The bilinear selectors are
    triangular hats: the weight of level column w for tap xx is
    relu(1 - |w - (px0 + xx)|) — exactly (1-fx) at the left corner, fx at
    the right, 0 elsewhere and 0 for every out-of-level tap, which is the
    reference's zeros-padding rule. In level-0 units that hat is
    2^-l * relu(2^l - |2^l (w + r - xx) - cx|): the distances and the
    heights are constants of the placement, the per-query work is
    subtract, abs, subtract, max on a splat of cx, and the two 2^-l wait
    in the level mask. y first: the 9 hats of every level on the shelf,
    stacked 9 rows a level, contract the shelf's rows in one dot; its
    result still mixes levels lane by lane, so a constant mask keeps for
    level l's rows only level l's own lanes (times 4^-l) — this is what
    makes a window hanging past a level's edge read zeros and never the
    neighbouring level; then ONE (16, lanes) block of x hats — each lane
    belongs to one level — contracts the lanes. The (16, 9L) result has a
    query's taps for x-offset xx in row xx: 9 row gathers a query move them
    to the scratch, where the stack of one 128-lane pad a level needed 36
    (and 16 selector tiles a query where this builds 7)."""
    centre = c_ref[0]
    cx, cy = centre[:, :, 0:1], centre[:, :, 1:2]  # (TP, 1, 1)
    n = 2 * radius + 1
    nsel = _up(n, 8)  # rows of the x hats: whole sublane tiles
    tp, _, lanes = corr_ref.shape[1:]
    slab = n * len(metas)  # scratch lanes an x-offset owns: (level, yy)
    corr_all = corr_ref[0].astype(jnp.float32)  # (TP, rows, lanes)
    # levels without a cell (tiny inputs pool to 0x0) read the
    # zeros-padding region in every tap and contribute nothing to the
    # projection; zero the scratch lanes they own
    for lvl, m in enumerate(metas):
        if m.rows == 0 or m.width == 0:
            for k in range(n):
                taps_ref[:, k * slab + lvl * n:k * slab + (lvl + 1) * n] = (
                    jnp.zeros((tp, n), jnp.float32))
    for row_off, rows, held in _shelves(metas):
        g = _up(n * len(held), 8)  # y-hat rows: 9 a level, to a whole tile
        yrow = jax.lax.broadcasted_iota(jnp.int32, (1, g, rows), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (1, g, rows), 2)
        trow = jax.lax.broadcasted_iota(jnp.int32, (1, g, lanes), 1)
        tlane = jax.lax.broadcasted_iota(jnp.int32, (1, g, lanes), 2)
        xx = jax.lax.broadcasted_iota(jnp.int32, (1, nsel, lanes), 1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, nsel, lanes), 2)
        # constants of the placement; a distance of +inf is a hat of 0
        ydist = jnp.full((1, g, rows), jnp.inf, jnp.float32)
        ytop = jnp.zeros((1, g, rows), jnp.float32)
        own = jnp.zeros((1, g, lanes), jnp.float32)
        xdist = jnp.full((1, nsel, lanes), jnp.inf, jnp.float32)
        xtop = jnp.zeros((1, nsel, lanes), jnp.float32)
        for i, (lvl, m) in enumerate(held):
            up = float(1 << lvl)
            mine = (yrow >= i * n) & (yrow < (i + 1) * n)
            ydist = jnp.where(
                mine, (row + (radius + i * n) - yrow).astype(jnp.float32) * up,
                ydist)
            ytop = jnp.where(mine, up, ytop)
            own = jnp.where(
                (trow >= i * n) & (trow < (i + 1) * n)
                & (tlane >= m.lane_off) & (tlane < m.lane_off + m.width),
                1.0 / (up * up), own)
            mine = ((lane >= m.lane_off) & (lane < m.lane_off + m.width)
                    & (xx < n))
            xdist = jnp.where(
                mine, (lane - (m.lane_off - radius) - xx).astype(jnp.float32)
                * up, xdist)
            xtop = jnp.where(mine, up, xtop)
        shelf = jax.lax.slice_in_dim(corr_all, row_off, row_off + rows,
                                     axis=1)
        yw = jnp.maximum(ytop - jnp.abs(ydist - cy), 0.0)  # (TP, g, rows)
        t = jax.lax.dot_general(       # (TP, g, lanes)
            yw, shelf, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        xw = jnp.maximum(xtop - jnp.abs(xdist - cx), 0.0)  # (TP, 16, lanes)
        taps = jax.lax.dot_general(    # (TP, 16x, g) — blended tap windows
            xw, t * own, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        first, width = held[0][0] * n, len(held) * n
        for k in range(n):  # lane-sliced stores into VMEM scratch
            taps_ref[:, k * slab + first:k * slab + first + width] = (
                taps[:, k, :width])
    acc = jax.lax.dot_general(  # ONE rank-2 projection matmul off scratch
        taps_ref[...], w_ref[...].astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    out_ref[0] = jnp.maximum(acc + b_ref[...], 0.0)


@functools.partial(jax.jit, static_argnames=("metas", "radius", "interpret"))
def _corr_lookup_proj_flat(stacked: jnp.ndarray,
                           metas: Tuple[ProjMeta, ...],
                           centres: jnp.ndarray,
                           weight: jnp.ndarray, bias: jnp.ndarray,
                           radius: int = 4, interpret: bool = False
                           ) -> jnp.ndarray:
    """Flat-query fused lookup+projection: stacked (1, Q, rows, lanes)
    plane, centres (1, Q, 2) level-0 (x, y), weight (L*(2r+1)^2, C),
    bias (C,). Returns (1, Q, C) = relu(lookup @ weight + bias)."""
    _, q, rows, lanes = stacked.shape
    n = 2 * radius + 1
    c_out = weight.shape[1]
    plane = rows * lanes * 4
    if 8 * plane > _VMEM_BLOCK_BYTES:
        raise ValueError(
            f"corr plane ({rows}x{lanes}) too large for any legal "
            "VMEM tile; use the unfused path (proj_lookup_supported "
            "gates this dispatch)")
    tp = _best_tile(q, min(_MAX_TILE_P, max(8, _VMEM_BLOCK_BYTES // plane)))
    qq = -(-q // tp) * tp
    if qq != q:
        stacked = jnp.pad(stacked, ((0, 0), (0, qq - q), (0, 0), (0, 0)))
        centres = jnp.pad(centres, ((0, 0), (0, qq - q), (0, 0)))
    # the scratch holds a query's taps x-offset slowest, then level, then
    # yy (one row gather an x-offset fills all levels): the weight's rows,
    # (level, xx, yy) in the lookup's channel order, follow it
    weight = weight.reshape(len(metas), n, n, c_out).transpose(
        1, 0, 2, 3).reshape(len(metas) * n * n, c_out)
    out = pl.pallas_call(
        functools.partial(_proj_kernel, radius=radius, metas=metas),
        name="corr_lookup_proj",  # the kernel's own name in a device trace
        grid=(qq // tp,),
        in_specs=[
            pl.BlockSpec((1, tp, 1, 2), lambda qi: (0, qi, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tp, rows, lanes), lambda qi: (0, qi, 0, 0),
                         memory_space=pltpu.VMEM),
            # constant index maps: Mosaic keeps these blocks resident
            # across grid steps (no per-program re-DMA)
            pl.BlockSpec((len(metas) * n * n, c_out), lambda qi: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c_out), lambda qi: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, tp, c_out), lambda qi: (0, qi, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, qq, c_out), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tp, len(metas) * n * n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(centres.astype(jnp.float32)[:, :, None, :], stacked,
      weight, bias.reshape(1, c_out))
    return out[:, :q]


def corr_lookup_proj(stacked: jnp.ndarray, metas: Tuple[ProjMeta, ...],
                     coords: jnp.ndarray, weight: jnp.ndarray,
                     bias: jnp.ndarray, radius: int = 4,
                     interpret: bool = False) -> jnp.ndarray:
    """Fused windowed lookup + convc1 projection + bias + relu over a
    pre-stacked pyramid (see :func:`stack_aligned_pyramid`).

    coords: (B, H, W, 2) level-0 (x, y); weight (L*(2r+1)^2, C) rows in
    the lookup's channel order; bias (C,). Returns (B, H, W, C) float32 =
    ``relu(corr_lookup(pyramid, coords) @ weight + bias)`` with the pair
    batch folded into the query dim (the lookup is purely per-query)."""
    b, h, w, _ = coords.shape
    flat = stacked.reshape(1, b * h * w, *stacked.shape[2:])
    out = _corr_lookup_proj_flat(flat, metas, coords.reshape(1, b * h * w, 2),
                                 weight, bias, radius, interpret)
    return out.reshape(b, h, w, -1)


def corr_lookup_proj_ref(pyramid: Sequence[jnp.ndarray], coords: jnp.ndarray,
                         weight: jnp.ndarray, bias: jnp.ndarray,
                         radius: int = 4) -> jnp.ndarray:
    """Pure-XLA reference of the fused projection (tests): the unfused
    composition relu(onehot_lookup @ W + b)."""
    corr = corr_lookup_onehot(pyramid, coords, radius)
    return jax.nn.relu(jnp.einsum("bhwk,kc->bhwc", corr, weight) + bias)


# ---- the one decision ------------------------------------------------------

class LookupForm(NamedTuple):
    """Which of the four forms a pyramid was prepared for. Static and
    hashable: RAFT's scan body carries it as its one lookup field."""
    impl: str  # 'proj' | 'level' | 'onehot' | 'gather'
    metas: Tuple[ProjMeta, ...] = ()  # 'proj': the stacked plane's levels
    fallback: Optional[str] = None  # why a size gate replaced 'proj'


def prepare_lookup(pyramid: Sequence[jnp.ndarray]
                   ) -> Tuple[Any, LookupForm]:
    """The lookup's one decision: which form runs on these raw
    (B, P, Hl, Wl) levels, and the pyramid in that form. Reads the backend
    and the level-0 plane's shape, nothing else. Off a TPU ``gather`` (raw
    levels). On a TPU ``proj`` where the one plane of all levels fits a
    VMEM tile (the plane; its metas ride the form), else ``level`` where
    every level does (aligned levels), else ``onehot`` (raw levels), with
    the reason the gate gave in ``fallback``.

    Call it ONCE, outside the GRU scan: the pads are loop-invariant and XLA
    does not hoist them out of the while body (unhoisted they cost ~30% of
    the RAFT forward, see :func:`align_level`)."""
    if jax.default_backend() != "tpu":
        return pyramid, LookupForm("gather")
    if proj_lookup_supported(pyramid):
        stacked, metas = stack_aligned_pyramid(pyramid)
        return stacked, LookupForm("proj", metas)
    hl, wl = pyramid[0].shape[2:]
    if pallas_lookup_supported(pyramid):
        return (tuple(align_level(c) for c in pyramid),
                LookupForm("level", fallback=(
                    f"the stacked {hl}x{wl} pyramid plane fits no legal "
                    "VMEM tile")))
    return pyramid, LookupForm("onehot", fallback=(
        f"a {hl}x{wl} level-0 plane fits no legal VMEM tile"))
