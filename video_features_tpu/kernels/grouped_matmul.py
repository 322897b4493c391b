"""The routed experts' grouped product as a Pallas kernel for the TPU.

``grouped_matmul(rows (length, K), weights (E, K, N), sizes (E,) int32)``
gives ``(length, N)`` in ``rows.dtype``: the first ``sizes[0]`` rows times
``weights[0]``, the next ``sizes[1]`` times ``weights[1]`` and so on,
accumulated in float32 over the whole of K and rounded once, which is what
``jax.lax.ragged_dot(rows, weights, sizes, preferred_element_type=
rows.dtype)`` states. A row past ``sizes.sum()`` belongs to no group: what
comes out there is whatever the buffer held (``ops/moe.py held_experts``
masks those rows), and no tile that lies wholly behind the last held row is
fetched or multiplied.

**The grid** is (column tiles, visits, K tiles). A visit is one (row tile,
group) pair that shares a row. They are listed on the device from ``sizes``
(:func:`visits`): a visit begins wherever a row tile begins or a group
begins, so there are at most ``length / tile + E`` of them, and the list is
handed to the kernel as prefetched scalars, which its index maps read. The
visits of one group follow each other and name the same block of weights,
which the pipeline then does not fetch again. A row tile that holds the end
of one group and the start of the next is visited once for each, and each
visit stores only its own group's rows. Inside a visit the kernel loops over
the tile's pieces of :data:`ROW_PIECE` rows: a piece with no row of the
visit's group is skipped, so a boundary costs a piece's work and not a
tile's, and the unrolled product in the kernel's code is a piece long.

**How the tiles follow the shape** (:func:`tiles_for`; nothing names a
model). The row tile is :data:`ROW_TILE` rows whatever the shape: every
group boundary inside a tile costs one more visit of the whole tile, so 64
groups over 98,304 rows cost a sixth more work at 256 rows and a third more
at 512, while 128 rows feed the MXU worse; a router that sends one expert
seven times the mean and leaves others nearly empty changes neither count.
K stays whole wherever a column tile of 512 lanes (or all of N) fits beside
it: a group's weights are then read once a column tile, where a tiled K
reads them again at every visit. The column tile is the widest multiple of
128 that divides N (1,408 = 11 x 128 is taken whole or in 128s; no power of
two divides it) and keeps the blocks of one grid step, each held twice by
the pipeline, inside :data:`VMEM_BLOCK_BYTES`: the rows are read once a
column tile.

**Set-up.** :func:`grouped_matmul` is one module-level ``jax.jit``: a step
that calls it from every unrolled layer traces the kernel and lowers it to
one Mosaic module once a distinct shape, not once a call site. Pallas is
imported by :func:`ready`, which a token extractor on a TPU calls under a
start-up phase of its own before its step is traced; importing this module
imports no Pallas, so :func:`grouped_matmul_supported` costs the CPU
nothing.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

LANES = 128
#: rows of a row tile (the module docstring: what a boundary costs)
ROW_TILE = 256
#: rows the kernel multiplies at a time: a loop over a tile's pieces, not one
#: product of the whole tile unrolled, keeps the kernel's code, which every
#: call site carries into the executable and every start loads, small; and a
#: piece with no row of the visit's group is skipped
ROW_PIECE = 128
#: what the blocks of one grid step may take. Mosaic gives a kernel 16 MiB of
#: VMEM unasked on a v5e and keeps some for itself (compiled for a described
#: v5e, blocks of 14.4 MiB fit and blocks of 15.0 do not); asking for more
#: (``vmem_limit_bytes``) takes it from the fusions of the rest of the
#: program, which lost more than the wider block won (PERF.md section 6,
#: PR 37)
VMEM_BLOCK_BYTES = 29 * 2**19

_pl = _pltpu = None


def ready() -> None:
    """Import Pallas and what its TPU lowering imports at its first use,
    about a second in all: here and not at this module's import, so that
    only a process that will run the kernel pays it, and outside a step's
    trace, so that the second is the caller's to name (the token extractors'
    ``startup.phase("kernels")``)."""
    global _pl, _pltpu
    if _pl is None:
        from jax.experimental import pallas
        from jax.experimental.pallas import tpu
        try:    # what the lowering rule would import at its first call,
            # inside the step's lowering; private, so its absence is no fault
            from jax._src.pallas.mosaic import pallas_call_registration  # noqa: F401
        except ImportError:
            pass
        _pl, _pltpu = pallas, tpu


class Tiles(NamedTuple):
    rows: int
    k: int
    n: int


def _divisors(width: int):
    """The multiples of 128 that divide ``width``, widest first."""
    return [d for d in range(width, 0, -LANES) if width % d == 0]


def _block_bytes(tiles: Tiles, k: int, itemsize: int) -> int:
    """The rows', weights' and output's blocks, each held twice (the
    pipeline fetches a step's blocks while the step before computes); a
    tiled K adds the float32 accumulator and the product beside it."""
    tm, tk, tn = tiles
    return (2 * (tm * tk + tk * tn + tm * tn) * itemsize
            + (0 if tk == k else 2 * tm * tn * 4))


def tiles_for(k: int, n: int, itemsize: int) -> Optional[Tiles]:
    """The (row, K, column) tiles of a product of ``K``-wide rows with
    ``N``-wide weights, or ``None`` where a width is no multiple of 128
    lanes. The length plays no part: a row's bits are the same in a long
    buffer and a short one."""
    if k % LANES or n % LANES:
        return None
    # no column tile under 512 while a narrower K allows one: the rows are
    # read once a column tile, and a tiled K writes the whole accumulator
    # at every step
    for narrowest in (min(n, 512), LANES):
        for tk in _divisors(k):
            for tn in _divisors(n):
                tiles = Tiles(ROW_TILE, tk, tn)
                if tn >= narrowest \
                        and _block_bytes(tiles, k, itemsize) <= VMEM_BLOCK_BYTES:
                    return tiles
    raise AssertionError("a block of 128 lanes fits")


def grouped_matmul_refusal(rows, weights) -> Optional[str]:
    """Why the kernel does not take this product (``rows`` (length, K) and
    ``weights`` (E, K, N): arrays or their shapes and types), or ``None``
    where it does. Reads the backend and the operands' static shapes,
    nothing else; ``ops/moe.py`` asks it once a product as the step is
    traced, and the extractors' ``moe`` event states what it said."""
    if jax.default_backend() != "tpu":
        return f"the backend is {jax.default_backend()}, not a TPU"
    (length, k), (_, _, n) = rows.shape, weights.shape
    if tiles_for(k, n, rows.dtype.itemsize) is None:
        return f"a width of {k} or {n} is no multiple of {LANES} lanes"
    if length % ROW_TILE:
        return f"{length} rows are no multiple of the {ROW_TILE}-row tile"
    if rows.dtype != weights.dtype:
        return f"{rows.dtype} rows against {weights.dtype} weights"
    return None


def grouped_matmul_supported(rows, weights) -> bool:
    """Whether the kernel takes this product: no refusal."""
    return grouped_matmul_refusal(rows, weights) is None


@functools.partial(jax.jit, static_argnames=("length", "tile"))
def visits(sizes: jnp.ndarray, length: int, tile: int):
    """``(tile_of, group_of, offsets, count)``: the (row tile, group) pairs
    that share a row, in the order of the rows. ``tile_of`` and ``group_of``
    have the static length ``length // tile + E``; the first ``count`` are
    visits and the rest repeat the last one. ``offsets`` (E + 1,) are the
    groups' first rows and, last, the rows held in all.

    A visit begins wherever a row tile begins or a group begins: both kinds
    of row are sorted into one list. A group that begins on a tile's first
    row begins no visit of its own, an empty group begins none, and neither
    does a tile behind the last held row. Jitted by itself: a layer's two
    products share one list, traced once and computed once."""
    tiles = length // tile
    ends = jnp.cumsum(sizes, dtype=jnp.int32)
    starts = ends - sizes
    never = jnp.int32(length)       # sorts behind every row of the buffer
    tile_rows = jax.lax.iota(jnp.int32, tiles) * tile
    group_rows = jnp.where(
        (sizes > 0) & (starts % tile != 0) & (starts < length), starts, never)
    begins = jax.lax.sort(jnp.concatenate(
        [jnp.where(tile_rows < ends[-1], tile_rows, never), group_rows]))
    count = jnp.sum(begins < never, dtype=jnp.int32)
    # behind the last visit the list repeats it: rows sort ascending, so
    # that is the largest row that begins one
    begins = jnp.minimum(begins, jnp.max(jnp.where(begins < never, begins, 0)))
    # the group a row lies in: how many groups end at or before it (an
    # empty group ends where it begins, so it is never the answer)
    group_of = jnp.minimum(
        jnp.sum(ends[None, :] <= begins[:, None], axis=1, dtype=jnp.int32),
        sizes.shape[0] - 1)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return begins // tile, group_of, offsets, count


def _kernel(tile_of, group_of, offsets, rows_ref, weights_ref, out_ref,
            *accumulator, k_tiles: int, piece: int):
    pl = _pl
    visit, k_step = pl.program_id(1), pl.program_id(2)
    group = group_of[visit]
    first, behind = offsets[group], offsets[group + 1]
    row0 = tile_of[visit] * out_ref.shape[0]

    def hand_over(here, top, product):
        # the group's own rows are stored; the others keep what another
        # visit of the tile left there (a piece no boundary cuts keeps none)
        row = top + jax.lax.broadcasted_iota(jnp.int32, product.shape, 0)
        out_ref[here, :] = jnp.where((row >= first) & (row < behind),
                                     product.astype(out_ref.dtype),
                                     out_ref[here, :])

    def one_piece(index, _):
        at = pl.multiple_of(index * piece, piece)
        here, top = pl.ds(at, piece), row0 + at

        # a piece that holds no row of this group is another visit's, or
        # nobody's: it costs this visit no MXU work
        @pl.when((top < behind) & (top + piece > first))
        def _():
            # bfloat16 operands go to the MXU as they are whatever matmul
            # precision the process pins for float32 (Mosaic refuses them
            # under "highest")
            product = jnp.dot(
                rows_ref[here, :], weights_ref[...],
                preferred_element_type=jnp.float32,
                precision=None if rows_ref.dtype == jnp.float32
                else jax.lax.Precision.DEFAULT)
            if k_tiles == 1:
                hand_over(here, top, product)
                return
            acc_ref, = accumulator

            @pl.when(k_step == 0)
            def _():
                acc_ref[here, :] = product

            @pl.when(k_step > 0)
            def _():
                acc_ref[here, :] += product

            @pl.when(k_step == k_tiles - 1)
            def _():
                hand_over(here, top, acc_ref[here, :])

    jax.lax.fori_loop(0, out_ref.shape[0] // piece, one_piece, None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_matmul(rows: jnp.ndarray, weights: jnp.ndarray,
                   sizes: jnp.ndarray, interpret: bool = False
                   ) -> jnp.ndarray:
    """``rows`` (length, K) times ``weights`` (E, K, N) by ``sizes`` (E,)
    int32 -> (length, N) in ``rows.dtype`` (the module docstring has the
    contract). Widths are multiples of 128 and ``length`` of
    :data:`ROW_TILE` (:func:`grouped_matmul_supported`). ``interpret`` runs
    the kernel in Pallas's interpreter, for the tests on the CPU."""
    ready()
    pl, pltpu = _pl, _pltpu
    (length, k), (e, _, n) = rows.shape, weights.shape
    tiles = tiles_for(k, n, rows.dtype.itemsize)
    if tiles is None or length % tiles.rows:
        raise ValueError(
            f"no tiles for ({length}, {k}) rows against ({e}, {k}, {n}) "
            "weights: grouped_matmul_supported gates this call")
    tm, tk, tn = tiles
    k_tiles = k // tk
    tile_of, group_of, offsets, count = visits(sizes, length, tm)
    return pl.pallas_call(
        functools.partial(_kernel, k_tiles=k_tiles, piece=ROW_PIECE),
        name="grouped_matmul",      # the kernel's own name in a device trace
        out_shape=jax.ShapeDtypeStruct((length, n), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, count, k_tiles),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda j, v, s, tile_of, group_of,
                             offsets: (tile_of[v], s)),
                pl.BlockSpec((None, tk, tn), lambda j, v, s, tile_of,
                             group_of, offsets: (group_of[v], s, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, v, s, tile_of,
                                   group_of, offsets: (tile_of[v], j)),
            scratch_shapes=([pltpu.VMEM((tm, tn), jnp.float32)]
                            if k_tiles > 1 else []),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * length * k * n, transcendentals=0,
            bytes_accessed=(length * k * (n // tn) + e * k * n + length * n)
            * rows.dtype.itemsize),
        interpret=interpret,
    )(tile_of, group_of, offsets, rows, weights)
