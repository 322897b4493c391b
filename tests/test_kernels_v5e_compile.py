"""The program's own kernels compiled for a described v5e, no chip attached.

Interpret mode (tests/test_kernels.py) checks values; it cannot see what the
chip's compiler refuses: a slice off the tiling, a block or a spill area
past VMEM. The TPU's compiler is installed here and compiles for a chip
that is described and not attached, about two seconds a kernel, so the
geometries at which ``place_levels`` answers differently are held here at
their real sizes. Nothing runs: no value and no time comes from this file.

The topology is described inside a fixture and nowhere while a module is
imported: one process at a time may load the TPU's library, and under
several workers only the one that is given this file may load it.
"""
import functools
import importlib
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml

from video_features_tpu.kernels import corr_lookup as cl


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


#: (h8, w8, pairs): the benchmark cell's 240x320 at its batch, the flow
#: stream's 224x224 at four stacks, Sintel's 436x1024 (level 0 alone on
#: its shelf), 1080x1920 (two shelves of 256 lanes, an 8-query tile) and
#: a 64x64 input whose plane is one 8-row shelf
@pytest.mark.parametrize("h8, w8, pairs", [
    (30, 40, 128), (28, 28, 256), (55, 128, 16), (135, 240, 1), (8, 8, 16)])
def test_proj_kernel_compiles_for_a_v5e(one_chip, h8, w8, pairs):
    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    levels = [(h8 >> i, w8 >> i) for i in range(4)]
    metas, (rows, lanes) = cl.place_levels(levels)
    assert cl.proj_lookup_supported(
        [jax.ShapeDtypeStruct((1, 1) + lv, jnp.float32) for lv in levels])
    q = pairs * h8 * w8
    compiled = cl._corr_lookup_proj_flat.lower(
        spec(1, q, rows, lanes), metas, spec(1, q, 2), spec(324, 256),
        spec(256)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert f"f32[1,{q},256]" in text


#: (length, K, N, experts, tiles): both products of DeepSeek-V2-Lite's cell
#: and of LFM2-8B-A1B's (every expert held: one length), of granite's and of
#: Nemotron-3-Super's (the compact buffer and the buffer with room for every
#: assignment; nemotron's non-gated products run in its 1,024-wide latent
#: over 128 short groups), K whole at all of them; and a K so wide that it
#: is tiled, with the accumulator in VMEM
@pytest.mark.parametrize("length, k, n, experts, tiles", [
    (98304, 2048, 2816, 64, (256, 2048, 1408)),
    (98304, 1408, 2048, 64, (256, 1408, 2048)),
    (65536, 2048, 3584, 32, (256, 2048, 896)),
    (65536, 1792, 2048, 32, (256, 1792, 1024)),
    (102400, 4096, 1536, 36, (256, 4096, 512)),
    (102400, 768, 4096, 36, (256, 768, 2048)),
    (163840, 4096, 1536, 36, (256, 4096, 512)),
    (163840, 768, 4096, 36, (256, 768, 2048)),
    (112640, 1024, 2688, 128, (256, 1024, 2688)),
    (112640, 2688, 1024, 128, (256, 2688, 1024)),
    (360448, 1024, 2688, 128, (256, 1024, 2688)),
    (360448, 2688, 1024, 128, (256, 2688, 1024)),
    (4096, 8192, 4096, 8, (256, 4096, 512))])
def test_grouped_matmul_compiles_for_a_v5e(one_chip, length, k, n, experts,
                                           tiles):
    from video_features_tpu.kernels import grouped_matmul as gm

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert gm.tiles_for(k, n, 2) == tiles
    compiled = gm.grouped_matmul.lower(
        spec((length, k)), spec((experts, k, n)),
        spec((experts,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert f"bf16[{length},{n}]" in text


def _loop_bodies(text):
    """The compiled HLO text of every ``while`` body of a program."""
    bodies = []
    for name in re.findall(r" while\(.*?body=(%[\w.\-]+)", text):
        start = text.index("\n" + name + " ")
        bodies.append(text[start:text.index("\n}\n", start)])
    return bodies


#: (family, rows, tokens): the attention layers of the four token cells at
#: their real widths; lfm2's and granite's repeat 8 key/value heads to 32,
#: nemotron's 2 to 32
ATTENTION_LAYERS = [
    ("lfm2_moe", 1, 16384), ("granite_hybrid", 4, 4096),
    ("deepseek_v2", 1, 16384), ("nemotron_h", 1, 16384)]


@functools.lru_cache(maxsize=None)
def _compiled_attention(one_chip, family, rows, tokens):
    """``(arch, key head width, HLO text)``: one attention layer of
    ``family`` at its published widths, compiled for a described v5e."""
    mod = importlib.import_module(f"video_features_tpu.models.{family}")
    published = yaml.safe_load((
        Path(__file__).resolve().parents[1] / "video_features_tpu" / "configs"
        / f"{family}.yml").read_text())["architecture"]
    arch = mod.arch_from_config(published, 1, 0)

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    # the layer, its weights, the key head's width and the rotary one (no
    # cos and sin where 0)
    if family == "deepseek_v2":
        layer, hd, rope = (mod.latent_attention, arch.qk_head_dim,
                           arch.qk_rope_head_dim)
        drawn = lambda: mod._draw_layer(arch, "dense", jax.random.key(0))[
            "attn"]
    else:
        layer, hd, rope = ((mod.attention, arch.head_dim, arch.head_dim)
                           if family == "lfm2_moe" else
                           (mod.attention_mixer, arch.head_dim, 0))
        drawn = lambda: mod._attention_weights(arch, jax.random.key(0))
    w = {k: spec(s.shape, s.dtype if s.ndim < 2 else jnp.bfloat16)
         for k, s in jax.eval_shape(drawn).items()}
    args = [w, spec((rows, tokens, arch.hidden_size)),
            spec((rows, tokens), jnp.int32)]
    if rope:
        args += [spec((rows, tokens, rope // 2), jnp.float32)] * 2
    return arch, hd, jax.jit(lambda *a: layer(arch, *a)).lower(
        *args).compile().as_text()


@pytest.mark.parametrize("family, rows, tokens", ATTENTION_LAYERS)
def test_no_attention_loop_rebuilds_the_keys_or_values_for_a_v5e(
        one_chip, family, rows, tokens):
    """The blocks ``blockwise_attention``'s loops index are made once,
    before them: no ``while`` body of the layer broadcasts or copies an
    array as large as all of K (left alone the compiler sank the repeat to
    every head, and two layout copies, into the scan over query tiles).
    The loop scores a tile of every head's 512 queries by 512 keys."""
    arch, hd, text = _compiled_attention(one_chip, family, rows, tokens)
    heads = arch.num_attention_heads
    all_of_k = rows * tokens * heads * hd
    bodies = _loop_bodies(text)
    assert any(f"f32[{heads},512,512]" in b for b in bodies)  # the scores
    for body in bodies:
        for line in body.splitlines():
            shape = re.search(r"= \w+\[([\d,]*)\]\S* (broadcast|copy)\(",
                              line)
            if shape and shape.group(1):
                assert np.prod([int(x) for x in shape.group(1).split(",")]
                               ) < all_of_k, line


def _loop_computations(text):
    """The compiled HLO text of every ``while`` body of a program and of
    every computation those bodies call (fusions, reductions, inner
    loops), each once."""
    seen, todo, found = set(), list(
        re.findall(r" while\(.*?body=(%[\w.\-]+)", text)), []
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        start = text.index("\n" + name + " ")
        found.append(text[start:text.index("\n}\n", start)])
        todo += re.findall(r"(?:calls|to_apply|body|condition)=(%[\w.\-]+)",
                           found[-1])
    return found


@pytest.mark.parametrize("family, rows, tokens", ATTENTION_LAYERS)
def test_no_attention_loop_masks_its_scores_again_for_a_v5e(
        one_chip, family, rows, tokens):
    """The fold reads no mask back out of its scores: ``exp(-inf)`` is 0,
    so no operation inside an attention loop tests a score tile for an
    infinity (``jit(isinf)``), and none packs the tile's heads into a
    ``u8``/``u16``/``u32`` mask of 512 x 512 (a test of every float32
    score, a pass over the tile as long as the exp-sum's, was in the loop
    until the fold dropped it). The row guards on the running max stay:
    16 or 32 heads x 512 values, not a tile."""
    arch, _, text = _compiled_attention(one_chip, family, rows, tokens)
    tile = 512 * 512
    row_guards = 0
    for body in _loop_computations(text):
        for line in body.splitlines():
            shape = re.search(r"= (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(", line)
            if not shape:
                continue
            size = np.prod([int(x) for x in shape.group(2).split(",") if x])
            if "jit(isinf)" in line:
                assert size < tile, line[:240]
                row_guards += 1
            if shape.group(3) == "reduce" and shape.group(1) in (
                    "u8", "u16", "u32"):
                assert size < tile, line[:240]
    assert row_guards      # the op names are still there to be read


def test_the_nemotron_step_compiles_for_a_v5e_and_fits_its_memory(
        one_chip, monkeypatch):
    """The whole step of ``nemotron-3-super-packed-resident`` (11 layers, 128
    of 512 experts held, one row of 16,384 tokens) for a described v5e: the
    grouped products of its five E layers in the Pallas kernel, in both
    branches of each layer's condition, and the arguments and temporaries
    inside the chip's 16.9 GB with room for what the process holds beside
    them (measured: 9.028 GB of arguments, 3.655 GB of temporaries). The
    kernel's gate asks the backend, which is the CPU here: the test opens
    it where the shapes allow the kernel."""
    from video_features_tpu.kernels import grouped_matmul as gm
    from video_features_tpu.models import nemotron_h as nem

    def by_shape(rows, weights):
        (length, k), (_, _, n) = rows.shape, weights.shape
        if gm.tiles_for(k, n, rows.dtype.itemsize) is None \
                or length % gm.ROW_TILE:
            return "no tiles"
        return None

    monkeypatch.setattr(gm, "grouped_matmul_refusal", by_shape)
    published = yaml.safe_load((
        Path(__file__).resolve().parents[1] / "video_features_tpu" / "configs"
        / "nemotron_h.yml").read_text())["architecture"]
    arch = nem.arch_from_config(dict(published, num_hidden_layers=11), 4, 0)
    assert arch.hybrid_override_pattern == "MEMEMEM*EME"
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda: nem.init_params(arch, 0, jnp.bfloat16)))
    rows = jax.ShapeDtypeStruct((1, 2, 16384), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda p, r: nem.segment_features(
        arch, 64, jnp.bfloat16, p, r)).lower(params, rows).compile()
    assert compiled.as_text().count("tpu_custom_call") == 5 * 2 * 2
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes == pytest.approx(9.028e9, rel=1e-3)
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 14e9


def _large_float32_copies(text, at_least=256e6):
    """The entry computation's float32 ``copy`` operations of ``at_least``
    bytes or more: each is an activation written out again in another
    layout."""
    start = text.index("\nENTRY ")
    entry = text[start:text.index("\n}\n", start)]
    found = []
    for line in entry.splitlines():
        shape = re.search(r"= f32\[([\d,]+)\]\S* copy\(", line)
        if shape and 4 * np.prod([int(x) for x in shape.group(1).split(",")]
                                 ) >= at_least:
            found.append(line.strip()[:160])
    return found


#: (family, rows, tokens, bytes the scan reads and writes, bytes one Mamba
#: layer does): nemotron's 128 heads x 64 over 8 groups of B/C, state 128,
#: chunk 128; granite's the same heads over one group, chunk 256, four rows
#: a dispatch
@pytest.mark.parametrize("family, rows, tokens, scan_bytes, layer_bytes", [
    ("nemotron_h", 1, 16384, 3.96e9, 11.43e9),
    ("granite_hybrid", 4, 4096, 3.69e9, 8.67e9)])
def test_the_mamba_layer_lays_out_no_float32_activation_twice_for_a_v5e(
        one_chip, family, rows, tokens, scan_bytes, layer_bytes):
    """``ssd_chunks`` and one ``mamba_mixer`` at the hybrid cells' widths
    for a described v5e: no float32 ``copy`` of 256 MB or more in either
    (a relaid activation, ``ops/ssd.py``'s layout rule), and XLA's ``bytes
    accessed`` within 10% of what the layout reaches. With the activations
    token-major (B, T, H, P) the scan read 9.12 GB (nemotron) and 7.22 GB
    (granite), a layer 20.8 GB and 16.2 GB, with five and three float32
    copies of 537 MB; in this layout 3.96 and 3.69 GB, 11.43 and 8.67."""
    from video_features_tpu.ops import ssd

    mod = importlib.import_module(f"video_features_tpu.models.{family}")
    published = yaml.safe_load((
        Path(__file__).resolve().parents[1] / "video_features_tpu" / "configs"
        / f"{family}.yml").read_text())["architecture"]
    arch = mod.arch_from_config(published, 1, 0)
    if family == "nemotron_h":
        h, p, n, groups, q = (arch.mamba_num_heads, arch.mamba_head_dim,
                              arch.ssm_state_size, (arch.n_groups,),
                              arch.chunk_size)
    else:
        h, p, n, groups, q = (arch.mamba_n_heads, arch.mamba_d_head,
                              arch.mamba_d_state, (), arch.mamba_chunk_size)

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    c = tokens // q
    scan = jax.jit(ssd.ssd_chunks).lower(
        spec((rows, c, h, p, q)), spec((rows, c, h, q), jnp.float32),
        spec((h,), jnp.float32), spec((rows, c, *groups, n, q)),
        spec((rows, c, *groups, n, q)), spec((rows, c, q), jnp.int32)
    ).compile()
    w = {k: spec(s.shape, s.dtype if s.ndim < 2 else jnp.bfloat16)
         for k, s in jax.eval_shape(
             lambda: mod._mamba_weights(arch, jax.random.key(0))).items()}
    layer = jax.jit(lambda *a: mod.mamba_mixer(arch, *a)).lower(
        w, spec((rows, tokens, arch.hidden_size)),
        spec((rows, tokens), jnp.int32)).compile()
    for compiled, reached in ((scan, scan_bytes), (layer, layer_bytes)):
        assert _large_float32_copies(compiled.as_text()) == []
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, list) else cost
        assert cost["bytes accessed"] <= 1.1 * reached
