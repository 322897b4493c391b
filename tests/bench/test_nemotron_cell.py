"""The ``nemotron-3-super-packed-resident`` cell's own files: the manifest, the
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

CONFIG = "nemotron-3-super-l11e128"
CELL = "nemotron-3-super-packed-resident"
MIX = "resident-packed-16k-8doc"
#: the catalog's row of NVIDIA-Nemotron-3-Super-120B-A12B-BF16, beside its URL
PUBLISHED = Path(__file__).parent / "fixtures" \
    / "nemotron-3-super.published.json"
#: the widths ``reduced`` may never name
WIDTHS = {"hidden_size": 4096, "head_dim": 128, "num_attention_heads": 32,
          "num_key_value_heads": 2, "mamba_num_heads": 128,
          "mamba_head_dim": 64, "ssm_state_size": 128, "n_groups": 8,
          "conv_kernel": 4, "chunk_size": 128, "expand": 2,
          "moe_intermediate_size": 2688, "moe_latent_size": 1024,
          "moe_shared_expert_intermediate_size": 5376,
          "num_experts_per_tok": 22}
#: what a row of 16,384 holds: the mid-quantiles of lognormal(1,792, 0.5)
DOCUMENTS = [832, 1150, 1403, 1656, 1939, 2288, 2792, 3859]
#: the cell's readers of a scope or a kernel, which a CPU trace leaves silent
SCOPED = ("step.model.attn_share", "step.model.ssm_share",
          "step.kernels.moe_experts_roofline")
#: what the cell joins: the token cells' readings and the Mamba share
METRICS = {"step.host.cpu_s_per_unit", "step.model.device_s_per_unit",
           "step.model.forward_roofline", "step.model.unscoped_share",
           "step.device.clock_bound_ms", *SCOPED}


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
    assert METRICS <= {p["name"] for p in cell.per_layer}
    # not the start-up ledger's: its block is pinned to four cells
    assert not any(p["name"].startswith("setup.") for p in cell.per_layer)
    assert cell.traffic["driver"] == "resident"
    assert (cell.traffic["resident_batches"], cell.traffic["block_s"],
            cell.traffic["trace_s"]) == (2, 2.0, 6.0)
    assert cell.traffic["check_video"] == {"kind": "tokens", "vocab": 32768,
                                           "zipf_s": 1.0}
    for attr in ("features", "control"):
        assert callable(cell.optional_config_function("references", attr))
    (entry,) = [c for c in m["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == config()["reduced"]


def test_every_published_width_is_unchanged_and_the_cut_is_stated():
    c = config()
    assert {k: c[k] for k in WIDTHS} == WIDTHS
    assert c["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                            "n_routed_experts", "vocab_size",
                            "num_nextn_predict_layers"]
    assert (c["num_hidden_layers"], c["hybrid_override_pattern"]) == (
        11, "MEMEMEM*EME")
    published = c["published"]
    assert published["num_hidden_layers"] == 88
    assert published["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    assert [published["hybrid_override_pattern"].count(k)
            for k in "ME*"] == [40, 40, 8]
    # one whole period: the published 40:40:8 ratio
    assert [c["hybrid_override_pattern"].count(k) for k in "ME*"] == [5, 5, 1]
    assert (published["n_routed_experts"], c["n_routed_experts"]) == (512,
                                                                      128)
    assert (published["vocab_size"], c["vocab_size"]) == (131072, 32768)
    deployment = c["deployment"]
    assert deployment["chips_that_share_each_layer"] == 4 == \
        c["run_keys"]["layer_shards"]
    assert c["run_keys"]["layer_shard_rank"] == 0
    assert deployment["pipeline_stages"] == 8
    assert deployment["layers_per_stage"] == [11] * 8
    assert "9.03 GB" in deployment["parameters_held"]
    assert c["unit"]["window"] == c["run_keys"]["stack_size"] == 16384
    assert c["check_units"] == c["run_keys"]["batch_size"] == 1
    assert 0.0 < c["measured"]["held_share"] < 1.0
    for key in ("selection_bias_scale", "precision", "router_and_latent"):
        assert key in c["assumed"]


def test_the_file_holds_the_sources_config_but_for_what_reduced_lists():
    row = json.loads(PUBLISHED.read_text())
    c = config()
    assert c["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if c.get(k) != v)
    assert differs == sorted(c["reduced"])
    assert {k: row["config"][k] for k in c["reduced"]} == c["published"]


def test_the_program_runs_the_architecture_the_file_states():
    """The program takes its widths from its own YAML and the cut from
    ``run_keys``: what it resolves to is what the file's top level says,
    with the held share of the experts and of the vocabulary."""
    from video_features_tpu.config import load_config
    from video_features_tpu.models import nemotron_h as nem
    c = config()
    args = load_config(c["family"], c["run_keys"])
    resolved = dict(args.architecture)
    for key, value in c.items():
        if key in resolved and key not in c["reduced"]:
            assert resolved[key] == value, key
    assert {k: resolved[k] for k in c["published"]
            if k != "num_hidden_layers"} == {
        k: v for k, v in c["published"].items() if k != "num_hidden_layers"}
    arch = nem.arch_from_config(resolved, args.layer_shards,
                                args.layer_shard_rank)
    assert arch.hybrid_override_pattern == c["hybrid_override_pattern"]
    assert (arch.experts_held, arch.first_expert, arch.vocab_held) == (
        128, 0, 32768)
    assert arch.counter_shape == (5, 512)
    assert (arch.d_inner, arch.conv_dim) == (8192, 10240)


# -- the costs, by hand, and the row they count ------------------------------------

def test_costs_of_a_row_by_hand():
    c = config()
    costs = config_file("costs").per_unit(c)
    t, d = 16384, 4096
    per_token = {k: v / t for k, v in costs["layers"].items()}
    # five Mamba layers: in_proj to [z | x B C | dt], out_proj
    assert per_token["mamba.in_proj"] == 5 * 2 * d * (8192 + 10240 + 128)
    assert per_token["mamba.out_proj"] == 5 * 2 * 8192 * d
    # one attention layer: 32 query heads over 2, 128 wide
    assert per_token["attn.q"] == 2 * d * 4096
    assert per_token["attn.k"] == per_token["attn.v"] == 2 * d * 256
    pairs = sum(n * (n + 1) // 2 for n in DOCUMENTS)
    assert costs["layers"]["attn.core"] == 32 * pairs * 2 * 256
    # five E layers: the shared unit and the latent projections on every
    # position, the held experts at the measured share of a document
    # token's 22 assignments
    assert per_token["moe.shared_in"] == per_token["moe.shared_out"] \
        == 5 * 2 * d * 5376
    assert per_token["moe.latent_down"] == 5 * 2 * d * 1024
    tokens = sum(DOCUMENTS)
    assert tokens == 15919
    share = c["measured"]["held_share"]
    assert costs["kernels"]["moe_experts"]["flops"] == pytest.approx(
        5 * tokens * 22 * share * 2 * 2 * 1024 * 2688)
    assert costs["layers"]["moe.experts"] == \
        costs["kernels"]["moe_experts"]["flops"]
    # the deployment's arithmetic: 4,513.9 M parameters, ~33.7 TFLOP a row
    assert costs["weight_elements"] == 4513945984
    assert costs["flops"] == pytest.approx(33.7e12, rel=0.01)
    parts = {k: sum(v for name, v in costs["layers"].items()
                    if name.startswith(k)) / costs["flops"]
             for k in ("mamba.", "moe.", "attn.")}
    assert 0.53 < parts["mamba."] < 0.56 and 0.39 < parts["moe."] < 0.42
    assert 0.04 < parts["attn."] < 0.05
    assert costs["flops"] / 197e12 > costs["bytes"] / 819e9    # compute-bound


def group(seed):
    return config_file("inputs").resident_batch(
        corpus.stream(seed, MIX, "batches"), (1, 2, 16384), np.int32)


def test_the_rows_lengths_are_fixed_under_every_seed_and_counted_by_costs():
    inputs, costs = config_file("inputs"), config_file("costs")
    mix = manifest.read_json(BENCH / "traffic" / f"{MIX}.json")
    assert mix["documents"]["length"] == {"dist": "lognormal",
                                          "median": 1792, "sigma": 0.5}
    assert mix["documents"]["count"] == 8
    assert inputs.lengths(2, 16384) == [DOCUMENTS, DOCUMENTS]
    orders = set()
    for seed in (2147484001, 5, 2**31 + 77):
        batch = group(seed)
        assert batch.dtype == np.int32 and batch.shape == (1, 2, 16384)
        seg = batch[0, 1].astype(np.int64)
        runs = np.diff(np.flatnonzero(np.diff(np.r_[-1, seg, -1]) != 0))
        ids = seg[np.r_[0, np.cumsum(runs)[:-1]]]
        # eight documents, then padding
        assert ids.tolist() == [1, 2, 3, 4, 5, 6, 7, 8, 0]
        assert sorted(runs[:8].tolist()) == DOCUMENTS
        assert runs[8] == 16384 - sum(DOCUMENTS) == 465
        orders.add(tuple(runs[:8]))
        assert int(sum(n * (n + 1) // 2 for n in runs[:8])) == \
            costs.causal_pairs(config())
        tokens = batch[0, 0]
        assert tokens.max() < 32768 and not tokens[seg == 0].any()
        assert np.bincount(tokens).argmax() == 0    # the same frequent id
    assert len(orders) > 1                          # the seed draws the order
    assert np.array_equal(group(5), group(5))


# -- the cell on the CPU at tiny widths -----------------------------------------------

#: the program's tiny architecture: four chips share a layer, as in the cell
PROGRAM = dict(hidden_size=64, num_hidden_layers=5,
               hybrid_override_pattern="MEM*E", vocab_size=2048,
               mamba_num_heads=8, mamba_head_dim=16, ssm_state_size=16,
               n_groups=4, chunk_size=32, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, n_routed_experts=32,
               num_experts_per_tok=6, moe_intermediate_size=128,
               moe_latent_size=32, moe_shared_expert_intermediate_size=96)
#: the share of a tiny E layer's assignments the 8 held experts take at the
#: tiny check item: 0.1823, rounded down
TINY_HELD_SHARE = 0.18


def add_nemotron_tiny(root: Path) -> dict:
    """The real files of the configuration under the name
    ``nemotron-tiny``, with a configuration of tiny widths and a mix with
    short blocks: new files and appended entries only. A row of 256 tokens
    packs eight documents of 13 to 60."""
    bench, m, before = copy_benchmark(root)
    tiny = config()
    tiny.update({k: v for k, v in PROGRAM.items()
                 if k not in ("n_routed_experts", "vocab_size")},
                name="nemotron-tiny", n_routed_experts=8, vocab_size=512)
    tiny["published"] = {**tiny["published"], "n_routed_experts": 32,
                         "vocab_size": 2048, "num_hidden_layers": 5,
                         "hybrid_override_pattern": "MEM*E"}
    tiny["measured"] = {"held_share": TINY_HELD_SHARE,
                        "held_share_source": "a CPU-sized stand-in"}
    tiny["unit"].update(window=256, stride=256)
    tiny["run_keys"].update(
        device="cpu", stack_size=256, step_size=256, max_segments=16,
        metrics_interval_s=1, architecture=dict(PROGRAM))
    (bench / "configs" / "nemotron-tiny.json").write_text(json.dumps(tiny))
    for kind in ("costs", "checks", "inputs", "references"):
        shutil.copy(bench / kind / f"{CONFIG}.py",
                    bench / kind / "nemotron-tiny.py")
    mix = manifest.read_json(bench / "traffic" / f"{MIX}.json")
    mix.update(block_s=0.3, trace_s=1.0)
    mix["check_video"]["vocab"] = 512
    (bench / "traffic" / "resident-packed-tiny-8doc.json").write_text(
        json.dumps(mix))
    m["configs"].append({
        "name": "nemotron-tiny", "source": tiny["source"],
        "file": "benchmark/configs/nemotron-tiny.json",
        "reduced": tiny["reduced"], "why": "a CPU-sized stand-in"})
    m["workloads"].append({
        "name": "nemotron-tiny-resident", "config": "nemotron-tiny",
        "traffic": "resident-packed-tiny-8doc", "chips": 1,
        "why": "a CPU-sized stand-in"})
    for metric in m["end_to_end"] + m["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("nemotron-tiny-resident")
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    nothing_edited(bench, before)
    return m


@pytest.fixture
def nemotron_root(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    add_nemotron_tiny(root)
    return root


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_tiny_cell_runs_through_run_py_on_the_cpu(
        nemotron_root, monkeypatch, capsys, trace):
    on_the_cpu(monkeypatch, nemotron_root)
    rc = bench_run.main(["--workload", "nemotron-tiny-resident", "--seed",
                         "2147484001", "--seconds", "3", "--trace", trace],
                        root=nemotron_root)
    line, out = last_line(capsys)
    assert rc == 0, out
    assert "a full group is (1, 2, 256) int32" in out
    assert "references/nemotron-tiny.py, handed the timed" in out
    assert line["failed"] == 0 and line["attempted"] > 0
    assert sorted(line["compared"]) == [
        "cosine_min", "held_share_off", "largest_expert_load_over_mean",
        "relative_error_max", "routing_moved_share"]
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


def test_the_float8_control_fails_where_the_program_passes(nemotron_root):
    """``compare()`` passes the reference against itself and the program's
    bfloat16 features of the check item, and fails the reference's float8
    control in their place; the reference in blocks of two experts is the
    reference in one block; ``validate()`` refuses a wrong
    ``expert_tokens``."""
    import jax
    from vftbench import program
    m = manifest.load_manifest(nemotron_root)
    cell = manifest.Cell(m, "nemotron-tiny-resident", nemotron_root)
    out_dir = nemotron_root / "benchmark_out"
    (check,) = corpus.build_fixed(
        out_dir, cell.traffic["check_video"],
        [corpus.frames_for(cell.config["check_units"], cell.config["unit"])],
        cell.corpus_kind(cell.traffic["check_video"])).values()
    extractor = program.build_extractor(
        program.program_args(cell.config, out_dir / "run"))
    ran = extractor.extract(check)
    validate = cell.config_function("checks", "validate")
    assert validate(ran, "nemotron_h", 1) is None
    assert "expert_tokens (1, 2, 31)" in validate(
        {**ran, "expert_tokens": ran["expert_tokens"][..., :31]},
        "nemotron_h", 1)
    assert "top-k" in validate(
        {**ran, "expert_tokens": ran["expert_tokens"]
         + np.eye(2, 32, dtype=int)}, "nemotron_h", 1)
    params = extractor.runner.params
    references = manifest.load_module(
        nemotron_root / "benchmark" / "references" / "nemotron-tiny.py")
    with jax.default_matmul_precision("highest"):
        reference, control = (getattr(references, name)(
            params, cell.config, check) for name in ("features", "control"))
        references.EXPERT_BLOCK = 2
        in_blocks = references.features(params, cell.config, check)
    assert reference["expert_tokens"].shape == (1, 2, 32)
    np.testing.assert_allclose(in_blocks["nemotron_h"],
                               reference["nemotron_h"], rtol=1e-5, atol=1e-6)
    assert np.array_equal(in_blocks["expert_tokens"],
                          reference["expert_tokens"])
    compare = cell.config_function("checks", "compare")
    itself, passed, failed = (compare(x, reference, "nemotron_h")
                              for x in (reference, ran, control))
    print("program", passed, "control", failed)
    assert itself["ok"] and itself["relative_error_max"] == 0.0
    assert passed["ok"] and not failed["ok"]
    assert failed["relative_error_max"] > 3 * passed["relative_error_max"]
    assert failed["cosine_min"] < passed["cosine_min"]
    # a tree that is not the loader's, rounded once, stops the check; so
    # does an expert of another chip's share
    broken = dict(params)
    broken["final_norm"] = broken["final_norm"] * 1.01
    with pytest.raises(AssertionError, match="rounded once"):
        references.features(broken, cell.config, check)
    layers = list(params["layers"])
    layers[1] = {**layers[1],
                 "experts_in": layers[1]["experts_in"][::-1]}
    with pytest.raises(AssertionError, match=r"experts 0:2"):
        references.features({**params, "layers": layers}, cell.config, check)
