"""The ``dsv2-lite-resident`` cell's own files: the manifest, the
configuration against its source and the program's defaults, the costs
against a hand count and against the row ``inputs/`` draws, the resident
groups, and the cell end to end on the CPU at tiny widths (the real
``costs/``, ``checks/``, ``inputs/`` and ``references/`` files under another
configuration's name), with the float8 control in the program's place.

A file of its own because a PR adds no line to a file the benchmark has."""
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

import run as bench_run
from vftbench import corpus, manifest

from .conftest import BENCH, REPO, copy_benchmark, nothing_edited
from .test_rehearsal import last_line, on_the_cpu

CONFIG = "deepseek-v2-lite-l7"
CELL = "dsv2-lite-resident"
MIX = "resident-window-16k"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
#: the widths ``reduced`` may never name
WIDTHS = {"hidden_size": 2048, "intermediate_size": 10944,
          "moe_intermediate_size": 1408, "kv_lora_rank": 512,
          "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
          "num_attention_heads": 16, "num_key_value_heads": 16,
          "n_routed_experts": 64, "n_shared_experts": 2,
          "num_experts_per_tok": 6, "vocab_size": 102400}


def config():
    return json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())


def config_file(kind):
    return manifest.load_module(BENCH / kind / f"{CONFIG}.py")


# -- the manifest and the configuration ------------------------------------------

def test_the_manifest_is_clean_and_the_cell_joins_it():
    m = manifest.load_manifest(REPO)
    assert manifest.check_manifest(m, REPO) == []
    cell = manifest.Cell(m, CELL, REPO)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        CONFIG, MIX, 1)
    assert {e["name"] for e in cell.end_to_end} == {"step_units_per_s",
                                                    "setup_s"}
    assert {p["name"] for p in cell.per_layer} == {
        "step.host.cpu_s_per_unit", "step.model.device_s_per_unit",
        "step.model.forward_roofline", "step.model.unscoped_share",
        "step.device.clock_bound_ms", "step.model.attn_share",
        "step.kernels.moe_experts_roofline", "step.model.mla_core_share",
        "step.kernels.mla_core_roofline", "step.model.experts_share"}
    assert cell.traffic["driver"] == "resident"
    assert (cell.traffic["resident_batches"], cell.traffic["block_s"],
            cell.traffic["trace_s"]) == (2, 2.0, 6.0)
    assert cell.traffic["check_video"] == {"kind": "tokens", "vocab": 102400,
                                           "zipf_s": 1.0}
    assert cell.corpus_kind(cell.traffic["check_video"]).SUFFIX == ".tokens"
    for attr in ("features", "control"):
        assert callable(cell.optional_config_function("references", attr))
    (entry,) = [c for c in m["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["num_hidden_layers"]


def test_every_published_width_is_unchanged_and_the_cut_is_stated():
    c = config()
    assert {k: c[k] for k in WIDTHS} == WIDTHS
    assert c["reduced"] == ["num_hidden_layers"]
    assert (c["num_hidden_layers"], c["published"]) == (
        7, {"num_hidden_layers": 27})
    assert c["deployment"]["chips_that_share_each_layer"] == 1 == \
        c["run_keys"]["layer_shards"]
    assert c["deployment"]["pipeline_stages"] == 4
    assert sum(c["deployment"]["layers_per_stage"]) == 27
    assert c["deployment"]["layers_per_stage"][0] == c["num_hidden_layers"]
    # the guide's floors: four layers behind the leading dense one, 8 experts
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4
    assert c["unit"]["window"] == c["run_keys"]["stack_size"] == 16384
    assert c["check_units"] == c["run_keys"]["batch_size"] == 1


def test_the_file_holds_the_sources_config_but_for_what_reduced_lists():
    if not CATALOG.is_file():
        pytest.skip("no catalog beside the model-configs guide here")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "DeepSeek-V2-Lite")
    c = config()
    assert c["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if c.get(k) != v)
    assert differs == sorted(c["reduced"])


def test_the_program_runs_the_architecture_the_file_states():
    """The program takes its widths from its own YAML and the cut from
    ``run_keys``: what it resolves to is what the file's top level says."""
    from video_features_tpu.config import load_config
    from video_features_tpu.models.deepseek_v2 import (arch_from_config,
                                                       softmax_scale,
                                                       yarn_bounds)
    c = config()
    args = load_config(c["family"], c["run_keys"])
    resolved = dict(args.architecture)
    for key, value in c.items():
        if key in resolved and key != "rope_scaling":
            assert resolved[key] == value, key
    assert dict(resolved["rope_scaling"]) == c["rope_scaling"]
    arch = arch_from_config(resolved, args.layer_shards,
                            args.layer_shard_rank)
    assert arch.layer_kinds == ("dense",) + ("moe",) * 6
    assert (arch.experts_held, arch.first_expert, arch.vocab_held) == (
        64, 0, 102400)
    assert arch.counter_shape == (6, 64)
    assert yarn_bounds(arch) == (10, 23)
    assert softmax_scale(arch) == pytest.approx(0.0721688 * 1.5896262,
                                                rel=1e-6)


# -- the costs, by hand, and the row they count ------------------------------------

def test_costs_of_a_row_by_hand():
    costs = config_file("costs").per_unit(config())
    t = 16384
    per_token = {k: v / t for k, v in costs["layers"].items()}
    assert per_token["attn.q"] == 7 * 2 * 2048 * 3072
    assert per_token["attn.kv_a"] == 7 * 2 * 2048 * 576
    assert per_token["attn.kv_b"] == 7 * 2 * 512 * 4096
    assert per_token["attn.o"] == 7 * 2 * 2048 * 2048
    # the core: 16 heads, scores over 192 and mixing over 128 a pair
    pairs = t * (t + 1) // 2
    assert costs["kernels"]["mla_core"]["pairs"] == pairs
    assert costs["layers"]["attn.core"] == 7 * 16 * pairs * 2 * 320
    assert costs["kernels"]["mla_core"]["flops"] == \
        costs["layers"]["attn.core"]
    assert per_token["dense.in"] + per_token["dense.out"] == \
        2 * 3 * 2048 * 10944
    # 6 expert layers at 6 assignments a token, 3 x 2,048 x 1,408 an expert
    assert costs["expected_assignments_a_token"] == 6.0
    assert per_token["moe.experts"] == 6 * 6 * 2 * 3 * 2048 * 1408
    assert costs["kernels"]["moe_experts"]["flops"] == \
        per_token["moe.experts"] * t
    assert per_token["moe.router"] == 6 * 2 * 2048 * 64
    assert per_token["moe.shared_in"] + per_token["moe.shared_out"] == \
        6 * 2 * 3 * 2048 * 2816
    # the issue's table: 3,799.8 M parameters, 28.6 TFLOP a row, MLA 45%
    assert costs["weight_elements"] == pytest.approx(3.7998e9, rel=1e-4)
    assert costs["weight_elements"] == (
        6 * (13_762_560 + 512 + 4096 + 64 * 8_650_752 + 17_301_504 + 131_072)
        + 13_762_560 + 512 + 4096 + 3 * 2048 * 10944 + 102400 * 2048 + 2048)
    assert costs["flops"] == pytest.approx(28.6e12, rel=0.005)
    mla = sum(costs["layers"][k] for k in costs["layers"]
              if k.startswith("attn."))
    assert 0.44 < mla / costs["flops"] < 0.46
    assert costs["flops"] / 197e12 > costs["bytes"] / 819e9    # compute-bound


def test_the_cores_pairs_are_those_of_the_row_inputs_draws():
    """``kernels.mla_core`` counts the causal same-document pairs of the
    drawn row, whatever its documents: the pairs of the segment ids
    themselves, counted the slow way."""
    inputs, costs = config_file("inputs"), config_file("costs")
    batch = inputs.resident_batch(corpus.stream(3, MIX, "batches"),
                                  (1, 2, 16384), np.int32)
    seg = batch[0, 1].astype(np.int64)
    runs = np.diff(np.flatnonzero(np.diff(np.r_[0, seg, 0]) != 0))
    by_hand = int(sum(n * (n + 1) // 2 for n in runs))
    assert by_hand == costs.causal_pairs(config()) == 16384 * 16385 // 2
    assert inputs.lengths(1, 16384) == [[16384]]


def group(seed):
    return config_file("inputs").resident_batch(
        corpus.stream(seed, MIX, "batches"), (1, 2, 16384), np.int32)


def test_a_group_is_the_same_under_one_seed_and_other_tokens_under_two():
    mix = manifest.read_json(BENCH / "traffic" / f"{MIX}.json")
    a, b, again = group(2147484001), group(5), group(5)
    assert np.array_equal(b, again) and not np.array_equal(a, b)
    assert mix["documents"] == {"count": 1, "length": 16384, "vocab": 102400,
                                "zipf_s": 1.0}
    for batch in (a, b):
        ids, seg = batch[:, 0], batch[:, 1]
        assert batch.dtype == np.int32 and batch.shape == (1, 2, 16384)
        assert (seg == 1).all()                  # one document, no padding
        assert ids.min() >= 0 and ids.max() < config()["vocab_size"]
        assert ids.max() > 50176                 # the whole vocabulary
        # the frequent ids are the same ones under every seed
        assert np.bincount(ids.ravel()).argmax() == 0


# -- the cell on the CPU at tiny widths -----------------------------------------------

TINY = dict(hidden_size=64, vocab_size=512, intermediate_size=96,
            moe_intermediate_size=32, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, num_attention_heads=4,
            num_key_value_heads=4, n_routed_experts=8, num_experts_per_tok=3,
            num_hidden_layers=3)
TINY_ROPE = dict(beta_fast=4, beta_slow=1, factor=4, mscale=0.707,
                 mscale_all_dim=0.707, original_max_position_embeddings=16,
                 type="yarn")


def add_deepseek_tiny(root: Path) -> dict:
    """The real files of the configuration under the name ``deepseek-tiny``,
    with a configuration of tiny widths and a mix with short blocks: new
    files and appended entries only. A window of 256 tokens: at 64 one
    near-tied expert swapped by bfloat16 is 0.26% of the 384 assignments,
    and three of them pass the real file's limit."""
    bench, m, before = copy_benchmark(root)
    tiny = config()
    tiny.update(TINY, name="deepseek-tiny", rope_scaling=TINY_ROPE)
    tiny["unit"].update(window=256, stride=256)
    tiny["run_keys"].update(
        device="cpu", stack_size=256, step_size=256, max_segments=16,
        metrics_interval_s=1,
        architecture={**TINY, "rope_scaling": TINY_ROPE})
    (bench / "configs" / "deepseek-tiny.json").write_text(json.dumps(tiny))
    for kind in ("costs", "checks", "inputs", "references"):
        shutil.copy(bench / kind / f"{CONFIG}.py",
                    bench / kind / "deepseek-tiny.py")
    mix = manifest.read_json(bench / "traffic" / f"{MIX}.json")
    mix.update(block_s=0.3, trace_s=1.0)
    mix["check_video"]["vocab"] = 512
    (bench / "traffic" / "resident-window-tiny.json").write_text(
        json.dumps(mix))
    m["configs"].append({
        "name": "deepseek-tiny", "source": tiny["source"],
        "file": "benchmark/configs/deepseek-tiny.json",
        "reduced": tiny["reduced"], "why": "a CPU-sized stand-in"})
    m["workloads"].append({
        "name": "deepseek-tiny-resident", "config": "deepseek-tiny",
        "traffic": "resident-window-tiny", "chips": 1,
        "why": "a CPU-sized stand-in"})
    for metric in m["end_to_end"] + m["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("deepseek-tiny-resident")
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    nothing_edited(bench, before)
    return m


@pytest.fixture
def deepseek_root(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    add_deepseek_tiny(root)
    return root


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_tiny_cell_runs_through_run_py_on_the_cpu(
        deepseek_root, monkeypatch, capsys, trace):
    on_the_cpu(monkeypatch, deepseek_root)
    rc = bench_run.main(["--workload", "deepseek-tiny-resident", "--seed",
                         "2147484001", "--seconds", "3", "--trace", trace],
                        root=deepseek_root)
    line, out = last_line(capsys)
    assert rc == 0, out
    assert "a full group is (1, 2, 256) int32" in out
    assert "references/deepseek-tiny.py, handed the timed" in out
    assert line["failed"] == 0 and line["attempted"] > 0
    assert sorted(line["compared"]) == [
        "cosine_min", "largest_expert_load_over_mean", "relative_error_max",
        "routing_moved_share"]
    assert line["correct"] is True, out
    if trace == "0":
        assert sorted(line["metrics"]) == ["setup_s", "step_units_per_s"]
        assert line["metrics"]["step_units_per_s"]["value"] > 0
    else:
        # the stand-in trace names no scope and no kernel: the new readers
        # find nothing, say so and raise nothing, as on a program without
        # them; what the harness reads itself is there
        assert {"step.host.cpu_s_per_unit",
                "step.model.device_s_per_unit"} <= set(line["metrics"])
        for silent in ("step.kernels.mla_core_roofline",
                       "step.model.mla_core_share",
                       "step.model.experts_share"):
            assert silent not in line["metrics"]
            assert f"{silent}: nothing to read" in out


def test_the_float8_control_fails_where_the_program_passes(deepseek_root):
    """``compare()`` passes the reference against itself and the program's
    bfloat16 features of the check item, and fails the reference's float8
    control in their place; ``validate()`` refuses a wrong
    ``expert_tokens``."""
    from vftbench import program
    m = manifest.load_manifest(deepseek_root)
    cell = manifest.Cell(m, "deepseek-tiny-resident", deepseek_root)
    out_dir = deepseek_root / "benchmark_out"
    (check,) = corpus.build_fixed(
        out_dir, cell.traffic["check_video"],
        [corpus.frames_for(cell.config["check_units"], cell.config["unit"])],
        cell.corpus_kind(cell.traffic["check_video"])).values()
    extractor = program.build_extractor(
        program.program_args(cell.config, out_dir / "run"))
    ran = extractor.extract(check)
    validate = cell.config_function("checks", "validate")
    assert validate(ran, "deepseek_v2", 1) is None
    assert "expert_tokens (1, 2, 7)" in validate(
        {**ran, "expert_tokens": ran["expert_tokens"][..., :7]},
        "deepseek_v2", 1)
    assert "top-k" in validate(
        {**ran, "expert_tokens": ran["expert_tokens"] + np.eye(2, 8, dtype=int)},
        "deepseek_v2", 1)
    import jax
    with jax.default_matmul_precision("highest"):
        reference, control = (
            cell.config_function("references", name)(
                extractor.runner.params, cell.config, check)
            for name in ("features", "control"))
    assert reference["expert_tokens"].shape == (1, 2, 8)
    compare = cell.config_function("checks", "compare")
    itself, passed, failed = (compare(x, reference, "deepseek_v2")
                              for x in (reference, ran, control))
    print("program", passed, "control", failed)
    assert itself["ok"] and itself["relative_error_max"] == 0.0
    assert passed["ok"] and not failed["ok"]
    assert failed["relative_error_max"] > passed["bands"][
        "relative_error_max"] > 2 * passed["relative_error_max"]
    assert failed["cosine_min"] < passed["bands"]["cosine_min"]
    # a tree that is not the loader's, rounded once, stops the check
    broken = dict(extractor.runner.params)
    broken["final_norm"] = broken["final_norm"] * 1.01
    with pytest.raises(AssertionError, match="rounded once"):
        cell.config_function("references", "features")(
            broken, cell.config, check)
