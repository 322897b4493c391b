"""The cost functions count what the algorithm needs, from shapes.

XLA's ``cost_analysis()`` is NOT the definition: it counts what the compiler
emitted (it leaves out taps that fall on padding, counts a scan body once and
changes with the compiler). The 78.74 GFLOP a clip the repo has quoted is such
a count; the shape count is compared with it loosely, and with hand arithmetic
exactly."""
import json

import pytest

from vftbench import manifest
from vftbench.shapes import Tally, out_len

from .conftest import BENCH


def costs_of(name):
    config = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    return config, manifest.load_function(
        BENCH / "costs" / f"{name}.py", "per_unit")(config)


def test_output_length_is_the_convolution_formula():
    assert out_len(112, 7, 2, 3) == 56
    assert out_len(56, 3, 1, 1) == 56
    assert out_len(56, 3, 2, 1) == 28
    assert out_len(16, 3, 2, 1) == 8
    assert out_len(240, 7, 2, 3) == 120


def test_a_convolution_is_two_operations_a_multiply_accumulate():
    t = Tally(act_bytes=2)
    t.conv("c", in_positions=100, out_positions=25, taps=9, cin=8, cout=16)
    assert t.flops == 2 * 25 * 9 * 8 * 16
    assert t.bytes == (100 * 8 + 25 * 16) * 2
    assert t.weights == 9 * 8 * 16
    assert t.per_unit(batch=4)["bytes"] == t.bytes + 9 * 8 * 16 * 2 / 4


def test_r21d_stem_and_first_block_by_hand():
    _, c = costs_of("r21d-18")
    # (1,7,7) 3 -> 45 at 16 x 56 x 56 outputs, then (3,1,1) 45 -> 64
    assert c["layers"]["stem.spatial"] == 2 * 16 * 56 * 56 * 49 * 3 * 45
    assert c["layers"]["stem.temporal"] == 2 * 16 * 56 * 56 * 3 * 45 * 64
    # midplanes(64, 64) = 64*64*27 // (64*9 + 3*64) = 144
    assert c["layers"]["layer1.0.conv1.spatial"] == \
        2 * 16 * 56 * 56 * 9 * 64 * 144
    assert c["layers"]["layer1.0.conv1.temporal"] == \
        2 * 16 * 56 * 56 * 3 * 144 * 64
    # stage 2 halves time and space and projects its shortcut
    assert c["layers"]["layer2.0.downsample"] == 2 * 8 * 28 * 28 * 64 * 128
    assert "layer1.0.downsample" not in c["layers"]


def test_r21d_total_is_near_the_published_and_the_quoted_counts():
    config, c = costs_of("r21d-18")
    assert sum(c["layers"].values()) == pytest.approx(c["flops"])
    # torchvision publishes 40.52 G multiply-accumulates for r2plus1d_18
    assert c["flops"] == pytest.approx(2 * 40.52e9, rel=0.03)
    # XLA's count of an older program, padding taps left out: loosely
    assert c["flops"] == pytest.approx(78.74e9, rel=0.15)
    assert c["weight_elements"] == pytest.approx(31.3e6, rel=0.1)  # no fc
    # compute-bound on the v5e: 197 TFLOP/s against 819 GB/s
    assert c["flops"] / 197e12 > c["bytes"] / 819e9
    assert config["run_keys"][config["batch_key"]] >= 1


def test_raft_gru_and_correlation_by_hand():
    config, c = costs_of("raft-sintel")
    arch = config["architecture"]
    p = (arch["height"] // 8) * (arch["width"] // 8)
    assert p == 1200
    # six 5-tap convolutions from 128 + 256 channels to 128, 20 iterations
    assert c["layers"]["gru"] == 2 * p * 5 * 384 * 128 * 6 * 20
    # all pairs: P x P dot products of 256 features
    assert c["layers"]["corr_volume"] == 2 * p * p * 256
    # the lookup reads (2r+1)^2 = 81 samples at each of 4 levels
    assert c["layers"]["motion.convc1"] == 2 * p * 324 * 256 * 20
    kernel = c["kernels"]["corr_lookup"]
    assert kernel["flops"] == pytest.approx(
        20 * (p * 324 * 8 + 2 * p * 324 * 256))
    # the kernel moves bytes, not operations: memory-bound on the v5e
    assert kernel["bytes"] / 819e9 > kernel["flops"] / 197e12


def test_raft_scales_with_iterations_and_area():
    config, base = costs_of("raft-sintel")
    per_unit = manifest.load_function(BENCH / "costs" / "raft-sintel.py",
                                      "per_unit")
    half = json.loads(json.dumps(config))
    half["architecture"]["iters"] = 10
    per_iter = (base["flops"] - per_unit(half)["flops"]) / 10
    assert per_iter * 20 < base["flops"] < per_iter * 20 * 1.4
    big = json.loads(json.dumps(config))
    big["architecture"].update(height=480, width=640)
    grown = per_unit(big)
    # convolutions grow 4x with the area, the all-pairs volume 16x
    assert grown["layers"]["gru"] == pytest.approx(4 * base["layers"]["gru"])
    assert grown["layers"]["corr_volume"] == pytest.approx(
        16 * base["layers"]["corr_volume"])
