"""Parity tests for the Pallas TPU kernels (interpret mode on CPU).

Each kernel is checked against the framework's pure-XLA implementation of the
same op, which is itself golden-tested against the torch reference
(test_pwc.py, test_raft.py) — so agreement here chains to reference parity.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from video_features_tpu.kernels.cost_volume import cost_volume_xla
from video_features_tpu.kernels.corr_lookup import (corr_lookup_onehot,
                                                    corr_lookup_pallas)
from video_features_tpu.models.raft import (build_corr_pyramid,
                                             corr_lookup_gather)

pytestmark = pytest.mark.quick


@pytest.mark.parametrize("b,h,w,c", [(1, 7, 9, 3), (2, 5, 12, 16)])
def test_cost_volume_matches_reference_semantics(rng, b, h, w, c):
    """Pin the XLA cost volume to the reference CUDA kernel's contract
    (correlation.py:47-115): channel (dy+4)*9+(dx+4) = channel-mean of
    f1 * shift(f2, dy, dx), zero padding — via an explicit numpy loop.
    (The Pallas twin was measured tied with XLA on v5e and deleted in
    round 5; see kernels/cost_volume.py docstring.)"""
    r = 4
    f1 = rng.normal(size=(b, h, w, c)).astype(np.float32)
    f2 = rng.normal(size=(b, h, w, c)).astype(np.float32)
    got = np.asarray(cost_volume_xla(jnp.asarray(f1), jnp.asarray(f2), r))
    assert got.shape == (b, h, w, (2 * r + 1) ** 2)
    f2p = np.pad(f2, ((0, 0), (r, r), (r, r), (0, 0)))
    for dy in (-r, 0, 1, r):
        for dx in (-r, -1, 0, r):
            win = f2p[:, r + dy:r + dy + h, r + dx:r + dx + w]
            want = (f1 * win).mean(axis=-1)
            ch = (dy + r) * (2 * r + 1) + (dx + r)
            np.testing.assert_allclose(got[..., ch], want,
                                       atol=1e-5, rtol=1e-5)


def test_cost_volume_bf16_accumulates_f32(rng):
    """bf16 inputs must not accumulate the 196-term channel sum in bf16:
    the result must track the f32 computation to bf16-rounding, not to
    bf16-accumulation (which would be ~1% off)."""
    f1 = rng.normal(size=(1, 6, 8, 196)).astype(np.float32)
    f2 = rng.normal(size=(1, 6, 8, 196)).astype(np.float32)
    exact = np.asarray(cost_volume_xla(jnp.asarray(f1), jnp.asarray(f2)))
    bf = np.asarray(cost_volume_xla(
        jnp.asarray(f1).astype(jnp.bfloat16),
        jnp.asarray(f2).astype(jnp.bfloat16)), dtype=np.float32)
    # input rounding to bf16 costs ~0.4% on a mean of 196 unit-normal
    # products; bf16 ACCUMULATION would cost several times that
    np.testing.assert_allclose(bf, exact, atol=2e-2)


def _pyramid_and_coords(rng, b=1, h8=12, w8=10, c=64):
    f1 = rng.normal(size=(b, h8, w8, c)).astype(np.float32)
    f2 = rng.normal(size=(b, h8, w8, c)).astype(np.float32)
    pyramid = build_corr_pyramid(jnp.asarray(f1), jnp.asarray(f2))
    # coords spread across (and slightly beyond) the image so both in-range
    # bilinear blending and the zeros-padding boundary path are exercised
    coords = rng.uniform(-6.0, max(h8, w8) + 6.0,
                         size=(b, h8, w8, 2)).astype(np.float32)
    return pyramid, jnp.asarray(coords), (h8, w8)


def test_corr_lookup_onehot_matches_gather(rng):
    pyramid, coords, _ = _pyramid_and_coords(rng)
    ref = np.asarray(corr_lookup_gather(pyramid, coords))
    ours = np.asarray(corr_lookup_onehot(pyramid, coords))
    assert ours.shape == ref.shape
    np.testing.assert_allclose(ours, ref, atol=1e-5, rtol=1e-5)


def test_corr_lookup_onehot_integer_coords(rng):
    """Integer coords hit the fx=fy=0 degenerate corner weights."""
    pyramid, _, (h8, w8) = _pyramid_and_coords(rng)
    b = pyramid[0].shape[0]
    gx, gy = np.meshgrid(np.arange(w8, dtype=np.float32),
                         np.arange(h8, dtype=np.float32))
    coords = jnp.asarray(np.broadcast_to(
        np.stack([gx, gy], -1), (b, h8, w8, 2)))
    ref = np.asarray(corr_lookup_gather(pyramid, coords))
    ours = np.asarray(corr_lookup_onehot(pyramid, coords))
    np.testing.assert_allclose(ours, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("geometry", [
    dict(),
    # tiny inputs pool down to 1x1 and then 0x0 levels: the kernel has to
    # reproduce the gather's all-zeros semantics for both
    dict(h8=6, w8=5, c=16),
], ids=["12x10", "degenerate"])
def test_corr_lookup_pallas_matches_gather(rng, geometry):
    pyramid, coords, _ = _pyramid_and_coords(rng, **geometry)
    if geometry:
        shapes = [tuple(c.shape[2:]) for c in pyramid]
        assert (1, 1) in shapes and (0, 0) in shapes, shapes
    ref = np.asarray(corr_lookup_gather(pyramid, coords))
    ours = np.asarray(corr_lookup_pallas(pyramid, coords, interpret=True))
    assert ours.shape == ref.shape
    np.testing.assert_allclose(ours, ref, atol=1e-5, rtol=1e-5)


def _pooled_volume_pyramid(f1, f2, levels=4):
    """The independent reference: the all-pairs volume, then torch
    avg_pool2d(2, stride=2) over the volume itself (corr.py:13-27) — what
    build_corr_pyramid did before its levels became correlations against
    the pooled second feature map."""
    b, h, w, c = f1.shape
    corr = jnp.einsum("bpc,bqc->bpq", f1.reshape(b, h * w, c),
                      f2.reshape(b, h * w, c),
                      preferred_element_type=jnp.float32) / np.sqrt(c)
    corr = corr.reshape(b, h * w, h, w)
    pyramid = [corr]
    for _ in range(levels - 1):
        hl, wl = corr.shape[2] // 2 * 2, corr.shape[3] // 2 * 2
        corr = jax.lax.reduce_window(
            corr[:, :, :hl, :wl], 0.0, jax.lax.add, (1, 1, 2, 2),
            (1, 1, 2, 2), [(0, 0)] * 4) / 4.0
        pyramid.append(corr)
    return pyramid


#: /8 geometries the system produces (240x320, i3d's 224, 128x160, Sintel's
#: 440x1024) and one whose odd sizes drop a row and a column and pool to a
#: 0-sized level; channels 256 and 64 have a power-of-two root (f1 is
#: scaled), 48 has not (the result is)
PYRAMID_CASES = [(30, 40, 256), (28, 28, 256), (16, 20, 48), (55, 128, 64),
                 (7, 9, 256), (8, 8, 64)]


@pytest.fixture(scope="module",
                params=[(g, d) for g in PYRAMID_CASES
                        for d in ("float32", "bfloat16")],
                ids=lambda p: f"{p[0][0]}x{p[0][1]}x{p[0][2]}-{p[1]}")
def both_pyramids(request):
    """(build_corr_pyramid's, the pooled volume's, max|corr|), built once a
    case for the three tests that read them."""
    (h8, w8, c), dtype = request.param
    rng = np.random.default_rng(h8 * 1000 + w8)
    f1, f2 = (jnp.asarray(rng.normal(size=(1, h8, w8, c)).astype(np.float32)
                          ).astype(dtype) for _ in range(2))
    want = _pooled_volume_pyramid(f1, f2)
    return build_corr_pyramid(f1, f2), want, float(jnp.max(jnp.abs(want[0])))


def test_corr_pyramid_equals_the_pooled_volume(both_pyramids):
    """pool(<f1[p], f2[q]>) = <f1[p], pool(f2)[q']>: every level within
    1e-6 of max|corr| of the volume pooled directly, float32 and bfloat16
    features (a pooled f2 rounded to bf16 would be 1e-3 off), same shapes
    under the floor rule, the 0-sized level included."""
    got, want, top = both_pyramids
    assert [g.shape for g in got] == [w.shape for w in want]
    assert all(g.dtype == jnp.float32 for g in got)
    for lvl, (g, w) in enumerate(zip(got, want)):
        if w.size:
            assert float(jnp.max(jnp.abs(g - w))) <= 1e-6 * top, lvl
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))


def test_stacked_plane_of_the_pyramid_has_exact_zero_pads(both_pyramids):
    """The plane the fused lookup reads: every level's cells where its
    placement says, equal to the pooled volume's, and exactly zero in every
    other cell (the reference's out-of-range rule, and what keeps a window
    off the level beside it); as many cells as ``stacked_plane_cells``
    states."""
    from video_features_tpu.kernels.corr_lookup import (
        stack_aligned_pyramid, stacked_plane_cells)
    got, want, top = both_pyramids
    plane, metas = stack_aligned_pyramid(got)
    want_plane, want_metas = stack_aligned_pyramid(want)
    assert metas == want_metas and plane.shape == want_plane.shape
    assert float(jnp.max(jnp.abs(plane - want_plane))) <= 1e-6 * top
    h8, w8 = want[0].shape[2:]
    assert plane.shape[2] * plane.shape[3] == stacked_plane_cells(h8, w8)
    assert plane.shape[2] % 8 == 0 and plane.shape[3] % 128 == 0
    data = np.zeros(plane.shape[2:], bool)
    for m, level in zip(metas, got):
        assert (m.rows, m.width) == level.shape[2:] and m.row_off % 8 == 0
        cells = (slice(m.row_off, m.row_off + m.rows),
                 slice(m.lane_off, m.lane_off + m.width))
        assert not data[cells].any()  # no two levels share a cell
        data[cells] = True
        np.testing.assert_array_equal(np.asarray(plane)[0, :, *cells],
                                      np.asarray(level)[0])
    assert not data.all() and not np.asarray(plane)[:, :, ~data].any()


def test_corr_lookup_proj_on_the_pyramid_matches_the_pooled_volume(
        both_pyramids, rng):
    """The fused kernel (interpreter) over the new pyramid's plane against
    the XLA composition over the pooled volume."""
    from video_features_tpu.kernels.corr_lookup import (
        corr_lookup_proj, corr_lookup_proj_ref, stack_aligned_pyramid)
    got, want, _ = both_pyramids
    h8, w8 = want[0].shape[2:]
    coords = jnp.asarray(rng.uniform(-6.0, max(h8, w8) + 6.0,
                                     size=(1, h8, w8, 2)).astype(np.float32))
    wgt, bias = _proj_weight(rng)
    stacked, metas = stack_aligned_pyramid(got)
    ref = np.asarray(corr_lookup_proj_ref(want, coords, wgt, bias))
    ours = np.asarray(corr_lookup_proj(stacked, metas, coords, wgt, bias,
                                       interpret=True))
    np.testing.assert_allclose(ours, ref, atol=1e-5, rtol=1e-5)


def _proj_weight(rng, c_out=24):
    w = rng.normal(size=(4 * 81, c_out)).astype(np.float32) * 0.1
    b = rng.normal(size=(c_out,)).astype(np.float32)
    return jnp.asarray(w), jnp.asarray(b)


def test_corr_lookup_proj_matches_composition(rng):
    """The fused lookup+convc1 kernel (round-4 TPU default inside the RAFT
    scan) equals the unfused composition relu(lookup @ W + b)."""
    from video_features_tpu.kernels.corr_lookup import (
        corr_lookup_proj, corr_lookup_proj_ref, proj_lookup_supported,
        stack_aligned_pyramid)
    pyramid, coords, _ = _pyramid_and_coords(rng)
    assert proj_lookup_supported(pyramid)
    wgt, bias = _proj_weight(rng)
    stacked, metas = stack_aligned_pyramid(pyramid)
    ref = np.asarray(corr_lookup_proj_ref(pyramid, coords, wgt, bias))
    ours = np.asarray(corr_lookup_proj(stacked, metas, coords, wgt, bias,
                                       interpret=True))
    assert ours.shape == ref.shape
    np.testing.assert_allclose(ours, ref, atol=1e-5, rtol=1e-5)


def test_corr_lookup_proj_integer_and_oob_coords(rng):
    """fx=fy=0 degenerate bilinear weights (the hat selector's exact-1 peak)
    and fully out-of-range windows (zeros rule -> relu(bias))."""
    from video_features_tpu.kernels.corr_lookup import (
        corr_lookup_proj, corr_lookup_proj_ref, stack_aligned_pyramid)
    pyramid, _, (h8, w8) = _pyramid_and_coords(rng)
    b = pyramid[0].shape[0]
    gx, gy = np.meshgrid(np.arange(w8, dtype=np.float32),
                         np.arange(h8, dtype=np.float32))
    coords = np.broadcast_to(np.stack([gx, gy], -1),
                             (b, h8, w8, 2)).copy()
    coords[:, 0, :, :] = -50.0  # first row: windows fully out of range
    coords = jnp.asarray(coords)
    wgt, bias = _proj_weight(rng)
    stacked, metas = stack_aligned_pyramid(pyramid)
    ref = np.asarray(corr_lookup_proj_ref(pyramid, coords, wgt, bias))
    ours = np.asarray(corr_lookup_proj(stacked, metas, coords, wgt, bias,
                                       interpret=True))
    np.testing.assert_allclose(ours, ref, atol=1e-5, rtol=1e-5)
    want_oob = np.broadcast_to(np.maximum(np.asarray(bias), 0.0),
                               ours[:, 0].shape)
    np.testing.assert_allclose(ours[:, 0], want_oob, atol=1e-6)


def test_corr_lookup_proj_degenerate_pyramid(rng):
    """Tiny inputs pool down to 1x1 and 0x0 levels; the fused kernel skips
    the empty level (its taps are all in the zeros-padding region)."""
    from video_features_tpu.kernels.corr_lookup import (
        corr_lookup_proj, corr_lookup_proj_ref, stack_aligned_pyramid)
    pyramid, coords, _ = _pyramid_and_coords(rng, h8=6, w8=5, c=16)
    shapes = [tuple(c.shape[2:]) for c in pyramid]
    assert (1, 1) in shapes and (0, 0) in shapes, shapes
    wgt, bias = _proj_weight(rng)
    stacked, metas = stack_aligned_pyramid(pyramid)
    assert metas[-1].rows == 0 and metas[-1].width == 0
    ref = np.asarray(corr_lookup_proj_ref(pyramid, coords, wgt, bias))
    ours = np.asarray(corr_lookup_proj(stacked, metas, coords, wgt, bias,
                                       interpret=True))
    np.testing.assert_allclose(ours, ref, atol=1e-5, rtol=1e-5)


#: /8 geometry -> the (rows, lanes) of the plane the shelf rule answers:
#: one shelf while level 0 is at most half the lane width, level 0 alone
#: above one shelf of levels 1-3 when it fills it
PLANES = {(30, 40): (32, 128), (28, 28): (32, 128), (8, 8): (8, 128),
          (7, 9): (8, 128), (6, 5): (8, 128), (55, 128): (56 + 32, 128),
          (135, 240): (136 + 72, 256), (200, 256): (200 + 104, 256),
          (270, 480): (272 + 136, 512)}


@pytest.mark.parametrize("h8, w8", sorted(PLANES))
def test_stacked_plane_cells_is_the_built_plane_s(h8, w8):
    """The one owner of the geometry (the VMEM gate and the flow stream's
    HBM budget read it) states the cells of the plane that
    ``stack_aligned_pyramid`` builds; every level lies inside the plane on
    an 8-sublane shelf and no two share a cell. Shapes only."""
    from video_features_tpu.kernels import corr_lookup as cl
    raw = [jax.ShapeDtypeStruct((1, 4, h8 >> i, w8 >> i), jnp.float32)
           for i in range(4)]
    found = []

    def built(*levels):
        plane, metas = cl.stack_aligned_pyramid(levels)
        found.append(metas)
        return plane

    plane = jax.eval_shape(built, *raw)
    (metas,) = found
    assert plane.shape == (1, 4) + PLANES[h8, w8]
    assert cl.stacked_plane_cells(h8, w8) == plane.shape[2] * plane.shape[3]
    assert metas == cl.place_levels([r.shape[2:] for r in raw])[0]
    held = np.zeros(plane.shape[2:], int)
    for m, r in zip(metas, raw):
        assert (m.rows, m.width) == r.shape[2:] and m.row_off % 8 == 0
        assert m.row_off + m.rows <= plane.shape[2]
        assert m.lane_off + m.width <= plane.shape[3]
        held[m.row_off:m.row_off + m.rows,
             m.lane_off:m.lane_off + m.width] += 1
    assert held.max() == 1
    cells, fill = cl.plane_fill(metas)
    assert cells == held.size and fill == held.sum() / held.size


def test_the_shelf_rule_stacks_levels_that_fit_no_lane_width_together():
    """Where no two levels fit side by side the rule's answer is the plain
    sublane stack: every level at lane 0 under the one before it."""
    from video_features_tpu.kernels.corr_lookup import ProjMeta, place_levels
    metas, plane = place_levels([(30, 128), (15, 100), (7, 90), (3, 80)])
    assert metas == (ProjMeta(0, 30, 0, 128), ProjMeta(32, 15, 0, 100),
                     ProjMeta(48, 7, 0, 90), ProjMeta(56, 3, 0, 80))
    assert plane == (64, 128)


#: the level shapes of a /8 geometry, looked up by a handful of queries:
#: the kernel is per query, so the edge cases need no h8*w8 of them
EDGE_GEOMETRIES = [(30, 40), (28, 28), (8, 8), (55, 128), (7, 9), (6, 5)]


def _edge_coords(rng, h8, w8):
    """(1, 8, 16, 2) centres: a row along each edge of level 0 and up to
    12 cells outside it on every side (a window is 9 taps wide at every
    level, so each level's window hangs over its left, right and bottom
    edge somewhere), the rest anywhere in that range."""
    xy = rng.uniform(-12.0, [w8 + 12.0, h8 + 12.0],
                     size=(8, 16, 2)).astype(np.float32)
    xs = np.linspace(-12.0, w8 + 12.0, 16, dtype=np.float32)
    ys = np.linspace(-12.0, h8 + 12.0, 16, dtype=np.float32)
    xy[0, :, 0], xy[0, :, 1] = xs, h8 - 1.0       # along the bottom edge
    xy[1, :, 0], xy[1, :, 1] = xs, h8 + 2.5       # under it
    xy[2, :, 0], xy[2, :, 1] = 0.0, ys            # along the left edge
    xy[3, :, 0], xy[3, :, 1] = w8 - 1.0, ys       # along the right edge
    xy[4, :, 0], xy[4, :, 1] = w8 + 3.25, ys      # right of it
    return jnp.asarray(xy[None])


@pytest.mark.parametrize("h8, w8", EDGE_GEOMETRIES)
def test_corr_lookup_proj_matches_its_twins_past_every_edge(rng, h8, w8):
    """The kernel (interpreter) over the shelf plane against the XLA
    composition and against the reference's gather, centres up to 12 cells
    outside level 0 on every side."""
    from video_features_tpu.kernels.corr_lookup import (
        corr_lookup_proj, corr_lookup_proj_ref, stack_aligned_pyramid)
    coords = _edge_coords(rng, h8, w8)
    pyramid = [jnp.asarray(rng.normal(size=(1, 128, h8 >> i, w8 >> i))
                           .astype(np.float32)) for i in range(4)]
    wgt, bias = _proj_weight(rng)
    ours = np.asarray(corr_lookup_proj(*stack_aligned_pyramid(pyramid),
                                       coords, wgt, bias, interpret=True))
    ref = np.asarray(corr_lookup_proj_ref(pyramid, coords, wgt, bias))
    gathered = np.asarray(jax.nn.relu(jnp.einsum(
        "bhwk,kc->bhwc", corr_lookup_gather(pyramid, coords), wgt) + bias))
    np.testing.assert_allclose(ours, ref, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(ours, gathered, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("level", range(4))
@pytest.mark.parametrize("h8, w8", EDGE_GEOMETRIES)
def test_a_window_past_a_level_s_edge_reads_zeros_not_its_neighbour(
        rng, h8, w8, level):
    """Only ``level`` holds anything (7.0 in every cell) and the projection
    ignores that level's own taps: every other level's window, wherever it
    hangs over ``level``'s cells in the plane, must weigh them by exactly
    0, so the kernel returns relu(bias) to the bit."""
    from video_features_tpu.kernels.corr_lookup import (
        corr_lookup_proj, stack_aligned_pyramid)
    coords = _edge_coords(rng, h8, w8)
    pyramid = [jnp.full((1, 128, h8 >> i, w8 >> i),
                        7.0 if i == level else 0.0, jnp.float32)
               for i in range(4)]
    wgt, bias = _proj_weight(rng)
    wgt = wgt.at[level * 81:(level + 1) * 81].set(0.0)
    plane, metas = stack_aligned_pyramid(pyramid)
    assert float(plane.sum()) == 7.0 * pyramid[level].size
    ours = np.asarray(corr_lookup_proj(plane, metas, coords, wgt, bias,
                                       interpret=True))
    np.testing.assert_array_equal(
        ours, np.broadcast_to(np.maximum(np.asarray(bias), 0.0), ours.shape))


@pytest.mark.parametrize("backend, h8, w8, impl, fallback", [
    ("cpu", 30, 40, "gather", None),
    ("tpu", 30, 40, "proj", None),
    ("tpu", 28, 28, "proj", None),
    ("tpu", 55, 128, "proj", None),
    # 1080x1920: levels 1-3 share a shelf under level 0, 208 x 256 cells,
    # inside the 8-query tile that the stack of 264 rows was not
    ("tpu", 135, 240, "proj", None),
    # 1600x2048: shelves of 200 + 104 rows x 256 lanes pass the 8-query tile
    ("tpu", 200, 256, "level", "stacked 200x256 pyramid plane"),
    # 2160x3840: no 8-query tile holds even the level-0 plane
    ("tpu", 270, 480, "onehot", "270x480 level-0 plane"),
])
def test_prepare_lookup_decides_from_backend_and_geometry(
        monkeypatch, backend, h8, w8, impl, fallback):
    """The lookup's one decision reads the backend and the level-0 plane's
    shape, and hands the pyramid back in the form it chose. Shapes only:
    the volume at (270, 480) would be 67 GB."""
    from video_features_tpu.kernels import corr_lookup as cl
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    raw = tuple(jax.ShapeDtypeStruct((1, h8 * w8, h8 >> i, w8 >> i),
                                     jnp.float32) for i in range(4))
    forms = []

    def prepared(*levels):
        pyramid, form = cl.prepare_lookup(levels)
        forms.append(form)
        return pyramid

    out = jax.eval_shape(prepared, *raw)
    (form,) = forms
    assert form.impl == impl
    assert (form.fallback is None) if fallback is None \
        else fallback in form.fallback
    hash(form)  # static: RAFT's scan body carries it as a module field
    if impl == "proj":
        assert out.shape == (1, h8 * w8) + PLANES[h8, w8]
        assert form.metas == cl.place_levels([r.shape[2:] for r in raw])[0]
    elif impl == "level":
        assert form.metas == ()
        assert [o.shape[2:] for o in out] == [
            (-(-(h8 >> i) // 8) * 8, -(-(w8 >> i) // 128) * 128)
            for i in range(4)]
    else:
        assert form.metas == ()
        assert [o.shape for o in out] == [r.shape for r in raw]
