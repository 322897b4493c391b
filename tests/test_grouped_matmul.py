"""``kernels/grouped_matmul.py``: the routed experts' grouped product as a
Pallas kernel, in the interpreter on the CPU against ``jax.lax.ragged_dot``
in float32; which visits its grid makes; what its gate refuses; and the
set-up guard: a step that calls ``held_experts`` from every unrolled layer
traces the kernel and lowers it for the TPU once a distinct shape."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from video_features_tpu.kernels import grouped_matmul as gm
from video_features_tpu.ops import moe

TILE = gm.ROW_TILE


def product_inputs(seed, length, k, n, sizes, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    sizes = np.asarray(sizes, np.int32)
    rows = jnp.asarray(rng.standard_normal((length, k)), dtype)
    weights = jnp.asarray(rng.standard_normal((len(sizes), k, n))
                          / np.sqrt(k), dtype)
    return rows, weights, jnp.asarray(sizes)


def skewed(groups, rows, fullest=7.0, empty=(1, 2, 9)):
    """``groups`` sizes that sum to ``rows``: one holds ``fullest`` times the
    mean, ``empty`` hold nothing, the others share the rest unevenly: the
    shape of DeepSeek-V2-Lite's seeded router (7.36 times the mean)."""
    sizes = np.zeros(groups, np.int64)
    sizes[5] = int(fullest * rows / groups)
    rest = [g for g in range(groups) if g != 5 and g not in empty]
    share = np.arange(1, len(rest) + 1, dtype=np.float64) ** 2
    sizes[rest] = np.floor(share / share.sum() * (rows - sizes[5]))
    sizes[rest[-1]] += rows - sizes.sum()
    assert sizes.sum() == rows and sizes.max() == sizes[5]
    return sizes


#: name -> (length, K, N, sizes). The widths keep the cells' ratios, scaled
#: down: DeepSeek-V2-Lite's 2,048 -> 2,816 and 1,408 -> 2,048 (3 x 128 stands
#: for the 11 x 128 no power of two divides), granite's 4,096 -> 1,536 and
#: 768 -> 4,096
CASES = {
    "uniform_groups": (2048, 512, 768, [256] * 8),
    "uniform_groups_off_the_tiles": (2048, 384, 512, [250] * 8),
    "deepseek_skew_first_product": (3072, 512, 768, skewed(16, 3072)),
    "deepseek_skew_second_product": (3072, 384, 512, skewed(16, 3072)),
    "granite_first_product_rows_past_the_sum": (
        2560, 1024, 384, skewed(9, 2000, fullest=2.4, empty=())),
    "granite_second_product_rows_past_the_sum": (
        2560, 256, 1024, skewed(9, 2000, fullest=2.4, empty=())),
    "one_row_a_group": (512, 128, 128, [1, 1, 0, 1, 300, 1, 1, 1]),
    "nothing_held": (512, 128, 256, [0] * 4),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_gives_what_ragged_dot_gives(case):
    length, k, n, sizes = CASES[case]
    rows, weights, sizes = product_inputs(len(case), length, k, n, sizes)
    got = gm.grouped_matmul(rows, weights, sizes, interpret=True)
    assert got.shape == (length, n) and got.dtype == rows.dtype
    held = int(sizes.sum())
    with jax.default_matmul_precision("highest"):
        want = jax.lax.ragged_dot(rows, weights, sizes,
                                  preferred_element_type=jnp.float32)
    np.testing.assert_allclose(np.asarray(got[:held]),
                               np.asarray(want[:held]), rtol=2e-5, atol=2e-5)


def test_bfloat16_rows_accumulate_in_float32_and_round_once():
    rows, weights, sizes = product_inputs(3, 1024, 256, 384,
                                          skewed(12, 900), jnp.bfloat16)
    got = gm.grouped_matmul(rows, weights, sizes, interpret=True)
    assert got.dtype == jnp.bfloat16
    exact = jax.lax.ragged_dot(rows.astype(jnp.float32),
                               weights.astype(jnp.float32), sizes,
                               precision="highest")
    got = np.asarray(got[:900].astype(jnp.float32))
    want = np.asarray(exact[:900].astype(jnp.bfloat16).astype(jnp.float32))
    # a float32 sum in another order lands on the other side of a rounding
    # boundary now and then: one last place of bfloat16, in a few of 10^4.
    # A bfloat16 accumulator would move most of them
    assert np.mean(got != want) < 1e-3
    np.testing.assert_allclose(got, want, rtol=2.0 ** -7, atol=1e-3)


def test_a_rows_bits_do_not_depend_on_the_buffers_length():
    """The compact buffer (``moe/held``) and the one with room for every
    assignment (``moe/all``) give every held row the same bits."""
    sizes = skewed(8, 1500, fullest=2.4, empty=(3,))
    rows, weights, sizes = product_inputs(5, 2560, 384, 256, sizes)
    short = gm.grouped_matmul(rows[:1536], weights, sizes, interpret=True)
    full = gm.grouped_matmul(rows, weights, sizes, interpret=True)
    assert np.array_equal(np.asarray(short[:1500]), np.asarray(full[:1500]))


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks so small that the widths of a test take several column tiles
    and a tiled K; the jitted kernel is traced anew on both sides."""
    jax.clear_caches()
    monkeypatch.setattr(gm, "VMEM_BLOCK_BYTES", 6 * 2**20)
    yield
    jax.clear_caches()


def test_a_tiled_k_and_several_column_tiles_give_the_same_product(
        small_blocks):
    assert gm.tiles_for(4096, 1536, 4) == gm.Tiles(TILE, 512, 512)
    assert gm.tiles_for(256, 1536, 4) == gm.Tiles(TILE, 256, 768)
    for k in (4096, 256):
        rows, weights, sizes = product_inputs(k, 1024, k, 1536,
                                              skewed(12, 1000))
        got = gm.grouped_matmul(rows, weights, sizes, interpret=True)
        with jax.default_matmul_precision("highest"):
            want = jax.lax.ragged_dot(rows, weights, sizes)
        np.testing.assert_allclose(np.asarray(got[:1000]),
                                   np.asarray(want[:1000]),
                                   rtol=2e-5, atol=2e-5)


def test_the_tiles_follow_the_widths_of_both_cells():
    # K whole at all four; 1,408 = 11 x 128 is taken whole, never in powers
    # of two; the length plays no part
    assert gm.tiles_for(2048, 2816, 2) == gm.Tiles(TILE, 2048, 1408)
    assert gm.tiles_for(1408, 2048, 2) == gm.Tiles(TILE, 1408, 2048)
    assert gm.tiles_for(4096, 1536, 2) == gm.Tiles(TILE, 4096, 512)
    assert gm.tiles_for(768, 4096, 2) == gm.Tiles(TILE, 768, 2048)
    assert gm.tiles_for(1000, 2048, 2) is None
    for k, n in ((2048, 2816), (4096, 1536), (16384, 4096)):
        tiles = gm.tiles_for(k, n, 2)
        assert gm._block_bytes(tiles, k, 2) <= gm.VMEM_BLOCK_BYTES
        assert k % tiles.k == 0 and n % tiles.n == 0


def test_a_visit_begins_where_a_tile_or_a_group_begins():
    sizes = jnp.asarray([300, 0, 212, 100, 0, 700], jnp.int32)  # 1,312 held
    tile_of, group_of, offsets, count = gm.visits(sizes, 2048, 256)
    count = int(count)
    # tiles 0..5 hold a row; groups 2 (at 300), 3 (at 512, a tile's first
    # row: no visit of its own) and 5 (at 612) begin inside one
    assert count == 6 + 2
    assert tile_of.shape == group_of.shape == (2048 // 256 + 6,)
    assert list(zip(np.asarray(tile_of)[:count].tolist(),
                    np.asarray(group_of)[:count].tolist())) == [
        (0, 0), (1, 0), (1, 2), (2, 3), (2, 5), (3, 5), (4, 5), (5, 5)]
    assert np.asarray(offsets).tolist() == [0, 300, 300, 512, 612, 612, 1312]
    # behind the last visit the list repeats it: no other block is named
    assert set(np.asarray(tile_of)[count:].tolist()) == {5}
    assert set(np.asarray(group_of)[count:].tolist()) == {5}


def test_the_skew_costs_no_more_visits_than_the_groups():
    """One group seven times the mean and three empty ones: at most one
    visit more a group than the held tiles, whatever the sizes."""
    sizes = skewed(64, 98304 // 4, fullest=7.36, empty=(1, 2, 9))
    _, _, _, count = gm.visits(jnp.asarray(sizes, jnp.int32), 98304 // 4, 256)
    assert 96 <= int(count) <= 96 + 64 - 3 - 1


def test_the_gate_refuses_the_cpu_and_a_width_off_the_lanes(monkeypatch):
    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    rows, weights = spec(98304, 2048), spec(64, 2048, 2816)
    assert not gm.grouped_matmul_supported(rows, weights)
    assert "cpu" in gm.grouped_matmul_refusal(rows, weights)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gm.grouped_matmul_supported(rows, weights)
    assert gm.grouped_matmul_supported(spec(102400, 768), spec(36, 768, 4096))
    assert "1000" in gm.grouped_matmul_refusal(spec(1024, 1000),
                                               spec(8, 1000, 2048))
    assert not gm.grouped_matmul_supported(spec(1024, 2048),
                                           spec(8, 2048, 1000))
    assert "1000 rows" in gm.grouped_matmul_refusal(spec(1000, 2048),
                                                    spec(8, 2048, 2816))
    assert not gm.grouped_matmul_supported(
        spec(1024, 2048, dtype=jnp.float32), spec(8, 2048, 2816))


def test_the_moe_event_states_the_gates_answer(monkeypatch):
    w_in = jax.ShapeDtypeStruct((64, 2048, 2816), jnp.bfloat16)
    w_out = jax.ShapeDtypeStruct((64, 1408, 2048), jnp.bfloat16)
    stated = moe.stated_products(16384, 6, 64, jnp.bfloat16, w_in, w_out)
    assert stated["products"] == "ragged_dot" and stated["tiles"] is None
    assert "cpu" in stated["fallback"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe.stated_products(16384, 6, 64, jnp.bfloat16, w_in, w_out) == {
        "products": "pallas", "rows": 98304, "experts": 64,
        "widths": [[2048, 2816], [1408, 2048]], "fallback": None,
        "tiles": [[256, 2048, 1408], [256, 1408, 2048]]}
    half = moe.stated_products(
        16384, 10, 72, jnp.bfloat16,
        jax.ShapeDtypeStruct((36, 4096, 1536), jnp.bfloat16),
        jax.ShapeDtypeStruct((36, 768, 4096), jnp.bfloat16))
    assert (half["products"], half["rows"]) == ("pallas", 102400)


# -- the set-up guard ---------------------------------------------------------

T, K, D, INNER, WIDE, LAYERS = 1024, 4, 128, 128, 8, 6


@pytest.mark.parametrize("held, shapes", [(WIDE, 2), (WIDE // 2, 4)],
                         ids=["every_expert_held", "under_the_condition"])
def test_a_step_traces_and_lowers_the_kernel_once_a_shape(monkeypatch, held,
                                                          shapes):
    """Six unrolled layers call ``held_experts``: twelve products (twice
    that under the condition, whose two branches are both traced). With the
    kernel forced on and the step cross-lowered for the TPU, the kernel's
    body is traced once a distinct shape and the module's text holds one
    ``tpu_custom_call`` a distinct shape, not one a call site: a trace and a
    Mosaic lowering a call site were a second of every start (PR 37)."""
    jax.clear_caches()
    monkeypatch.setattr(gm, "grouped_matmul_refusal", lambda rows, w: None)
    traced = []
    body = gm._kernel

    def counted(*refs, **static):
        traced.append(tuple(r.shape for r in refs))
        return body(*refs, **static)

    monkeypatch.setattr(gm, "_kernel", counted)

    def step(u, gates, experts, valid, layers):
        for w_in, w_out in layers:
            with jax.named_scope("moe"):
                u = u + moe.held_experts(u, gates, experts, w_in, w_out, 0,
                                         valid, WIDE).astype(u.dtype)
        return u

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    lowered = jax.jit(step).trace(
        spec((T, D)), spec((T, K), jnp.float32), spec((T, K), jnp.int32),
        spec((T,), jnp.bool_),
        [(spec((held, D, 2 * INNER)), spec((held, INNER, D)))] * LAYERS
    ).lower(lowering_platforms=("tpu",))
    assert len(traced) == shapes and len(set(traced)) == shapes
    assert lowered.as_text().count("tpu_custom_call") == shapes
    jax.clear_caches()
