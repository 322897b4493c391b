"""The ``lfm2-8b-packed-resident`` cell's own files: the manifest, the
configuration against its source and the program's defaults, the costs
against a hand count and against the row ``inputs/`` draws, the resident
groups, and the cell end to end on the CPU at tiny widths (the real
``costs/``, ``checks/``, ``inputs/`` and ``references/`` files under another
configuration's name), with the float8 control in the program's place.

A file of its own, so the other cells' test files stay as they are. The
cell's metrics are checked as a subset of what it reports: the manifest may
later append the cell to another metric."""
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

import run as bench_run
from vftbench import corpus, manifest

from .conftest import BENCH, REPO, copy_benchmark, nothing_edited
from .test_rehearsal import last_line, on_the_cpu

CONFIG = "lfm2-8b-a1b-l12"
CELL = "lfm2-8b-packed-resident"
MIX = "resident-packed-16k"
#: the published ``config.json`` of LiquidAI/LFM2-8B-A1B, beside its URL
PUBLISHED = Path(__file__).parent / "fixtures" / "lfm2-8b-a1b.published.json"
#: the widths ``reduced`` may never name
WIDTHS = {"hidden_size": 2048, "intermediate_size": 7168,
          "moe_intermediate_size": 1792, "num_attention_heads": 32,
          "num_key_value_heads": 8, "num_experts": 32,
          "num_experts_per_tok": 4, "conv_L_cache": 3, "vocab_size": 65536}
#: the first twelve of the published 24 layer types
STAGE_0 = ["conv", "conv", "full_attention", "conv", "conv", "conv",
           "full_attention", "conv", "conv", "conv", "full_attention", "conv"]
#: what a row of 16,384 holds: the mid-quantiles of lognormal(3,584, 0.5)
DOCUMENTS = [2016, 3056, 4203, 6370]
#: the cell's readers of a scope or a kernel, which a CPU trace leaves silent
SCOPED = ("step.model.attn_share", "step.kernels.moe_experts_roofline")


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
    assert {"step_units_per_s", "setup_s"} <= \
        {e["name"] for e in cell.end_to_end}
    # among the cell's metrics: the token cells' readings
    assert {"step.host.cpu_s_per_unit", "step.model.device_s_per_unit",
            "step.model.forward_roofline", "step.model.unscoped_share",
            "step.device.clock_bound_ms", "step.model.attn_share",
            "step.kernels.moe_experts_roofline"} \
        <= {p["name"] for p in cell.per_layer}
    assert cell.traffic["driver"] == "resident"
    assert (cell.traffic["resident_batches"], cell.traffic["block_s"],
            cell.traffic["trace_s"]) == (2, 2.0, 6.0)
    assert cell.traffic["check_video"] == {"kind": "tokens", "vocab": 65536,
                                           "zipf_s": 1.0}
    for attr in ("features", "control"):
        assert callable(cell.optional_config_function("references", attr))
    (entry,) = [c for c in m["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["num_hidden_layers", "layer_types"]


def test_every_published_width_is_unchanged_and_the_cut_is_stated():
    c = config()
    assert {k: c[k] for k in WIDTHS} == WIDTHS
    assert c["reduced"] == ["num_hidden_layers", "layer_types"]
    assert (c["num_hidden_layers"], c["layer_types"]) == (12, STAGE_0)
    assert c["published"]["num_hidden_layers"] == 24
    assert c["published"]["layer_types"][:12] == STAGE_0
    assert c["published"]["layer_types"].count("conv") == 18
    deployment = c["deployment"]
    assert deployment["chips_that_share_each_layer"] == 1 == \
        c["run_keys"]["layer_shards"]
    assert deployment["pipeline_stages"] == 2
    assert deployment["layers_per_stage"] == [12, 12]
    assert "7.86 GB" in deployment["parameters_held"]
    # the floors of the cut: four layers behind the leading dense ones, whole
    # periods of the pattern (conv, conv, attention, conv) three times over
    assert c["num_hidden_layers"] - c["num_dense_layers"] >= 4
    assert STAGE_0.count("full_attention") == 3
    assert c["unit"]["window"] == c["run_keys"]["stack_size"] == 16384
    assert c["check_units"] == c["run_keys"]["batch_size"] == 1
    assert c["assumed"]["expert_bias_scale"] == "0.05"


def test_the_file_holds_the_sources_config_but_for_what_reduced_lists():
    row = json.loads(PUBLISHED.read_text())
    c = config()
    assert c["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if c.get(k) != v)
    assert differs == sorted(c["reduced"])
    assert {k: row["config"][k] for k in c["reduced"]} == c["published"]


def test_the_program_runs_the_architecture_the_file_states():
    """The program takes its widths from its own YAML and the cut from
    ``run_keys``: what it resolves to is what the file's top level says."""
    from video_features_tpu.config import load_config
    from video_features_tpu.models import lfm2_moe as lfm
    c = config()
    args = load_config(c["family"], c["run_keys"])
    resolved = dict(args.architecture)
    for key, value in c.items():
        if key in resolved and key != "layer_types":
            assert resolved[key] == value, key
    assert list(resolved["layer_types"]) == c["published"]["layer_types"]
    arch = lfm.arch_from_config(resolved, args.layer_shards,
                                args.layer_shard_rank)
    assert list(arch.layer_types) == c["layer_types"]
    assert arch.layer_kinds[:3] == ("conv/dense", "conv/dense", "attn/moe")
    assert (arch.experts_held, arch.first_expert, arch.vocab_held) == (
        32, 0, 65536)
    assert arch.counter_shape == (10, 32)
    assert arch.head_dim == 64


# -- the costs, by hand, and the row they count ------------------------------------

def test_costs_of_a_row_by_hand():
    c = config()
    costs = config_file("costs").per_unit(c)
    t, d = 16384, 2048
    per_token = {k: v / t for k, v in costs["layers"].items()}
    # nine conv layers: in_proj to 3 D, the gates and three taps, out_proj
    assert per_token["conv.in_proj"] == 9 * 2 * d * 3 * d
    assert per_token["conv.out_proj"] == 9 * 2 * d * d
    assert per_token["conv.taps"] == 9 * d * 7
    # three attention layers: 32 query heads over 8, 64 wide
    assert per_token["attn.q"] == 3 * 2 * d * 2048
    assert per_token["attn.k"] == per_token["attn.v"] == 3 * 2 * d * 512
    pairs = sum(n * (n + 1) // 2 for n in DOCUMENTS)
    assert costs["layers"]["attn.core"] == 3 * 32 * pairs * 2 * 128
    # the routed layers count the document tokens, not the padding
    tokens = sum(DOCUMENTS)
    assert tokens == 15645
    assert costs["kernels"]["moe_experts"]["flops"] == \
        10 * tokens * 4 * 2 * 3 * d * 1792
    assert costs["layers"]["moe.experts"] == \
        costs["kernels"]["moe_experts"]["flops"]
    assert per_token["dense.in"] + per_token["dense.out"] == \
        2 * 2 * 3 * d * 7168
    # the deployment's arithmetic: 3,928.7 M parameters, ~24 TFLOP a row, the
    # experts most of it
    assert costs["weight_elements"] == pytest.approx(3.9287e9, rel=1e-4)
    assert costs["flops"] == pytest.approx(23.55e12, rel=0.005)
    share = {k: sum(v for name, v in costs["layers"].items()
                    if name.startswith(k)) / costs["flops"]
             for k in ("moe.", "conv.", "dense.", "attn.")}
    assert 0.57 < share["moe."] < 0.61 and 0.19 < share["conv."] < 0.23
    assert costs["flops"] / 197e12 > costs["bytes"] / 819e9    # compute-bound


def group(seed):
    return config_file("inputs").resident_batch(
        corpus.stream(seed, MIX, "batches"), (1, 2, 16384), np.int32)


def test_the_rows_lengths_are_fixed_under_every_seed_and_counted_by_costs():
    inputs, costs = config_file("inputs"), config_file("costs")
    mix = manifest.read_json(BENCH / "traffic" / f"{MIX}.json")
    assert mix["documents"]["length"] == {"dist": "lognormal",
                                          "median": 3584, "sigma": 0.5}
    assert inputs.lengths(2, 16384) == [DOCUMENTS, DOCUMENTS]
    orders = set()
    for seed in (2147484001, 5, 2**31 + 77):
        batch = group(seed)
        assert batch.dtype == np.int32 and batch.shape == (1, 2, 16384)
        seg = batch[0, 1].astype(np.int64)
        runs = np.diff(np.flatnonzero(np.diff(np.r_[-1, seg, -1]) != 0))
        ids = seg[np.r_[0, np.cumsum(runs)[:-1]]]
        # four documents, then padding
        assert ids.tolist() == [1, 2, 3, 4, 0]
        assert sorted(runs[:4].tolist()) == DOCUMENTS
        assert runs[4] == 16384 - sum(DOCUMENTS)
        orders.add(tuple(runs[:4]))
        by_hand = int(sum(n * (n + 1) // 2 for n in runs[:4]))
        assert by_hand == costs.causal_pairs(config())
        tokens = batch[0, 0]
        assert tokens.max() < 65536 and not tokens[seg == 0].any()
        assert np.bincount(tokens).argmax() == 0    # the same frequent id
    assert len(orders) > 1                          # the seed draws the order
    assert np.array_equal(group(5), group(5))


# -- the cell on the CPU at tiny widths -----------------------------------------------

TINY = dict(hidden_size=64, vocab_size=512, intermediate_size=96,
            moe_intermediate_size=32, num_attention_heads=4,
            num_key_value_heads=2, num_experts=8, num_experts_per_tok=3,
            num_hidden_layers=4, num_dense_layers=1,
            layer_types=["conv", "full_attention", "conv", "full_attention"])


def add_lfm2_tiny(root: Path) -> dict:
    """The real files of the configuration under the name ``lfm2-tiny``,
    with a configuration of tiny widths and a mix with short blocks: new
    files and appended entries only. A row of 256 tokens packs four documents of 32 to 100."""
    bench, m, before = copy_benchmark(root)
    tiny = config()
    tiny.update(TINY, name="lfm2-tiny")
    tiny["unit"].update(window=256, stride=256)
    tiny["run_keys"].update(
        device="cpu", stack_size=256, step_size=256, max_segments=16,
        metrics_interval_s=1, architecture=dict(TINY))
    (bench / "configs" / "lfm2-tiny.json").write_text(json.dumps(tiny))
    for kind in ("costs", "checks", "inputs", "references"):
        shutil.copy(bench / kind / f"{CONFIG}.py",
                    bench / kind / "lfm2-tiny.py")
    mix = manifest.read_json(bench / "traffic" / f"{MIX}.json")
    mix.update(block_s=0.3, trace_s=1.0)
    mix["check_video"]["vocab"] = 512
    (bench / "traffic" / "resident-packed-tiny.json").write_text(
        json.dumps(mix))
    m["configs"].append({
        "name": "lfm2-tiny", "source": tiny["source"],
        "file": "benchmark/configs/lfm2-tiny.json",
        "reduced": tiny["reduced"], "why": "a CPU-sized stand-in"})
    m["workloads"].append({
        "name": "lfm2-tiny-resident", "config": "lfm2-tiny",
        "traffic": "resident-packed-tiny", "chips": 1,
        "why": "a CPU-sized stand-in"})
    for metric in m["end_to_end"] + m["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("lfm2-tiny-resident")
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    nothing_edited(bench, before)
    return m


@pytest.fixture
def lfm2_root(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    add_lfm2_tiny(root)
    return root


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_tiny_cell_runs_through_run_py_on_the_cpu(
        lfm2_root, monkeypatch, capsys, trace):
    on_the_cpu(monkeypatch, lfm2_root)
    rc = bench_run.main(["--workload", "lfm2-tiny-resident", "--seed",
                         "2147484001", "--seconds", "3", "--trace", trace],
                        root=lfm2_root)
    line, out = last_line(capsys)
    assert rc == 0, out
    assert "a full group is (1, 2, 256) int32" in out
    assert "references/lfm2-tiny.py, handed the timed" in out
    assert line["failed"] == 0 and line["attempted"] > 0
    assert sorted(line["compared"]) == [
        "cosine_min", "largest_expert_load_over_mean", "relative_error_max",
        "routing_moved_share"]
    assert line["correct"] is True, out
    if trace == "0":
        assert {"setup_s", "step_units_per_s"} <= set(line["metrics"])
        assert line["metrics"]["step_units_per_s"]["value"] > 0
    else:
        # the stand-in trace names no scope and no kernel: their readers
        # find nothing, say so and raise nothing; what the harness reads
        # itself is there
        assert {"step.host.cpu_s_per_unit",
                "step.model.device_s_per_unit"} <= set(line["metrics"])
        for silent in SCOPED:
            assert silent not in line["metrics"]
            assert f"{silent}: nothing to read" in out


def test_the_float8_control_fails_where_the_program_passes(lfm2_root):
    """``compare()`` passes the reference against itself and the program's
    bfloat16 features of the check item, and fails the reference's float8
    control in their place; ``validate()`` refuses a wrong
    ``expert_tokens``."""
    from vftbench import program
    m = manifest.load_manifest(lfm2_root)
    cell = manifest.Cell(m, "lfm2-tiny-resident", lfm2_root)
    out_dir = lfm2_root / "benchmark_out"
    (check,) = corpus.build_fixed(
        out_dir, cell.traffic["check_video"],
        [corpus.frames_for(cell.config["check_units"], cell.config["unit"])],
        cell.corpus_kind(cell.traffic["check_video"])).values()
    extractor = program.build_extractor(
        program.program_args(cell.config, out_dir / "run"))
    ran = extractor.extract(check)
    validate = cell.config_function("checks", "validate")
    assert validate(ran, "lfm2_moe", 1) is None
    assert "expert_tokens (1, 3, 7)" in validate(
        {**ran, "expert_tokens": ran["expert_tokens"][..., :7]},
        "lfm2_moe", 1)
    assert "top-k" in validate(
        {**ran, "expert_tokens": ran["expert_tokens"] + np.eye(3, 8, dtype=int)},
        "lfm2_moe", 1)
    import jax
    with jax.default_matmul_precision("highest"):
        reference, control = (
            cell.config_function("references", name)(
                extractor.runner.params, cell.config, check)
            for name in ("features", "control"))
    assert reference["expert_tokens"].shape == (1, 3, 8)
    compare = cell.config_function("checks", "compare")
    itself, passed, failed = (compare(x, reference, "lfm2_moe")
                              for x in (reference, ran, control))
    print("program", passed, "control", failed)
    assert itself["ok"] and itself["relative_error_max"] == 0.0
    assert passed["ok"] and not failed["ok"]
    # at these widths the control fails by the moved share; on the chip, at
    # the cell's, by every precision limit (PERF.md): each of its readings
    # is worse than the program's here too
    assert failed["routing_moved_share"] > passed["bands"][
        "routing_moved_share"] > passed["routing_moved_share"]
    assert failed["relative_error_max"] > 5 * passed["relative_error_max"]
    assert failed["cosine_min"] < passed["cosine_min"]
    # a tree that is not the loader's, rounded once, stops the check
    broken = dict(extractor.runner.params)
    broken["final_norm"] = broken["final_norm"] * 1.01
    with pytest.raises(AssertionError, match="rounded once"):
        cell.config_function("references", "features")(
            broken, cell.config, check)
