"""The ``granite-h-resident`` cell's own files: the configuration against its
source and the program's defaults, the costs against a hand count, the
resident groups, the ``tokens`` corpus kind, and the cell end to end on the
CPU at tiny widths (the real ``costs/``, ``checks/``, ``inputs/`` and
``references/`` files under another configuration's name), with the float8
control in the program's place.

A file of its own because a PR adds no line to a file the benchmark has
(``test_costs.py``, ``conftest.py``)."""
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

import run as bench_run
from vftbench import corpus, manifest

from .conftest import BENCH, REPO, copy_benchmark, nothing_edited
from .test_rehearsal import last_line, on_the_cpu

CONFIG = "granite-4.0-h-small-l10e36"
CELL = "granite-h-resident"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
#: the widths ``reduced`` may never name
WIDTHS = {"hidden_size": 4096, "intermediate_size": 768,
          "shared_intermediate_size": 1536, "mamba_n_heads": 128,
          "mamba_d_head": 64, "mamba_d_state": 128, "mamba_d_conv": 4,
          "mamba_chunk_size": 256, "mamba_expand": 2, "mamba_n_groups": 1,
          "num_attention_heads": 32, "num_key_value_heads": 8,
          "num_experts_per_tok": 10}


def config():
    return json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())


def config_file(kind):
    return manifest.load_module(BENCH / kind / f"{CONFIG}.py")


# -- the configuration -------------------------------------------------------------

def test_every_published_width_is_unchanged_and_the_cut_is_stated():
    c = config()
    assert {k: c[k] for k in WIDTHS} == WIDTHS
    assert c["reduced"] == ["num_hidden_layers", "layer_types",
                            "num_local_experts", "vocab_size"]
    assert not set(c["reduced"]) & set(WIDTHS)
    assert (c["num_hidden_layers"], c["num_local_experts"],
            c["vocab_size"]) == (10, 36, 50176)
    assert c["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert c["published"]["num_local_experts"] == 72
    assert c["published"]["vocab_size"] == 100352
    assert c["deployment"]["chips_that_share_each_layer"] == 2 == \
        c["run_keys"]["layer_shards"]
    assert c["deployment"]["pipeline_stages"] == 4
    # the guide's floors: a whole period, 8 experts, an eighth of the rows
    assert c["num_local_experts"] >= 8
    assert c["vocab_size"] * 8 >= c["published"]["vocab_size"]
    assert c["unit"]["window"] == c["run_keys"]["stack_size"] == 4096
    assert c["check_units"] == c["run_keys"]["batch_size"] == 4


def test_the_file_holds_the_sources_config_but_for_what_reduced_lists():
    if not CATALOG.is_file():
        pytest.skip("no catalog beside the model-configs guide here")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "granite-4.0-h-small")
    c = config()
    assert c["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if c.get(k) != v)
    assert differs == sorted(c["reduced"])


def test_the_program_runs_the_architecture_the_file_states():
    """The program takes its widths from its own YAML and the cut from
    ``run_keys``: what it resolves to is what the file's top level says."""
    from video_features_tpu.config import load_config
    from video_features_tpu.models.granite_hybrid import arch_from_config
    c = config()
    args = load_config(c["family"], c["run_keys"])
    arch = arch_from_config(dict(args.architecture), args.layer_shards,
                            args.layer_shard_rank)
    assert list(arch.layer_types) == c["layer_types"]
    assert (arch.experts_held, arch.first_expert, arch.vocab_held) == \
        (c["num_local_experts"], 0, c["vocab_size"])
    assert arch.num_local_experts == c["published"]["num_local_experts"]
    for key in WIDTHS:
        if hasattr(arch, key):
            assert getattr(arch, key) == c[key], key
    for key in ("embedding_multiplier", "residual_multiplier",
                "attention_multiplier", "logits_scaling", "rms_norm_eps"):
        assert getattr(arch, key) == c[key], key


# -- the costs, by hand --------------------------------------------------------------

def test_costs_of_a_row_by_hand():
    costs = config_file("costs").per_unit(config())
    t = 4096
    per_token = {k: v / t for k, v in costs["layers"].items()}
    # 9 Mamba layers: 4096 x (2 x 8192 + 2 x 128 + 128) in, 8192 x 4096 out
    assert per_token["mamba.in_proj"] == 9 * 2 * 4096 * 16768
    assert per_token["mamba.out_proj"] == 9 * 2 * 8192 * 4096
    # the scan: scores over half a chunk once, then per head the mixing
    # over half a chunk and twice the (64 x 128) state
    scan = 9 * (2 * 128 * 128 + 128 * (2 * 64 * 128 + 4 * 64 * 128))
    assert per_token["mamba.ssd"] == scan
    assert costs["kernels"]["ssd_scan"]["flops"] == scan * t
    # 10 expert layers at 5 of a token's 10 assignments: 3 x 4096 x 768
    # weights an expert; the shared expert on every token
    assert costs["expected_assignments_a_token"] == 5.0
    assert per_token["moe.experts"] == 10 * 5 * 2 * 3 * 4096 * 768
    assert costs["kernels"]["moe_experts"]["flops"] == \
        per_token["moe.experts"] * t
    assert per_token["moe.router"] == 10 * 2 * 4096 * 72
    assert per_token["moe.shared_in"] + per_token["moe.shared_out"] == \
        10 * 2 * 3 * 4096 * 1536
    # attention: the causal half of the row, 32 heads of 128, scores + mix
    assert per_token["attn.core"] == 32 * 4 * 128 * (t + 1) / 2
    # the issue's planning estimate: 3.36 GFLOP a token, 4,757 M parameters
    assert costs["flops"] / t == pytest.approx(3.36e9, rel=0.01)
    assert costs["weight_elements"] == pytest.approx(4.757e9, rel=0.001)
    # compute-bound on a v5e: the weights are read once for four rows
    assert costs["flops"] / 197e12 > costs["bytes"] / 819e9
    share = (per_token["mamba.ssd"] + per_token["mamba.in_proj"]
             + per_token["mamba.out_proj"] + per_token["moe.experts"]
             + per_token["moe.router"]) / (costs["flops"] / t)
    assert 0.84 < share < 0.88       # the two mechanisms, ~86% of the step


# -- the resident groups and the corpus kind ------------------------------------------

def group(seed):
    inputs = config_file("inputs")
    return inputs.resident_batch(
        corpus.stream(seed, "resident-packed-4k", "batches"), (4, 2, 4096),
        np.int32)


def test_a_group_is_the_same_work_under_every_seed_and_other_tokens():
    mix = manifest.read_json(BENCH / "traffic" / "resident-packed-4k.json")
    a, b, again = group(2147484001), group(5), group(5)
    assert np.array_equal(b, again) and not np.array_equal(a, b)
    lengths = config_file("inputs").lengths(4, 4096)
    assert len(lengths) == mix["documents"]["count"] == 20
    assert (min(lengths), max(lengths), sum(lengths)) == (72, 3635, 16093)
    assert mix["documents"]["length"] == {"dist": "lognormal", "median": 512,
                                          "sigma": 1.0}
    assert mix["documents"]["vocab"] == config()["vocab_size"]
    for batch in (a, b):
        ids, seg = batch[:, 0], batch[:, 1]
        assert batch.dtype == np.int32 and ids.min() >= 0
        assert ids.max() < config()["vocab_size"]
        assert (seg > 0).sum() == 16093
        found = []
        for row in seg:
            # segments 1, 2, ... one after the other, padding at the end
            change = np.flatnonzero(np.diff(row)) + 1
            runs = np.split(row, change)
            assert [r[0] for r in runs if r[0]] == list(
                range(1, int(row.max()) + 1))
            assert all(r[0] == 0 for r in runs[int(row.max()):])
            found += [len(r) for r in runs if r[0]]
        assert sorted(found) == lengths
        # the frequent ids are the same ones under every seed
        assert np.bincount(ids[seg > 0]).argmax() == 0


def test_a_tokens_file_is_its_plan_and_zipf(tmp_path):
    kind = manifest.load_module(BENCH / "corpora" / "tokens.py")
    assert kind.SUFFIX == ".tokens" and set(kind.GEOMETRY) == {"vocab",
                                                               "zipf_s"}
    (path,) = corpus.build_fixed(tmp_path, {"kind": "tokens", "vocab": 50176},
                                 [16384], kind).values()
    ids = np.fromfile(path, "<i4")
    assert ids.shape == (16384,) and 0 <= ids.min() and ids.max() < 50176
    counts = np.bincount(ids, minlength=4)
    # Zipf(1): P(rank 1) = 1 / H(50176) = 8.8%, rank 2 half of that
    assert 0.07 < counts[0] / 16384 < 0.105 and counts[0] > counts[1] > \
        counts[3]
    again = corpus.build_fixed(tmp_path, {"kind": "tokens", "vocab": 50176},
                               [16384], kind)
    assert list(again.values()) == [path]


# -- the cell on the CPU at tiny widths -----------------------------------------------

TINY = dict(hidden_size=64, vocab_size=256, mamba_n_heads=8, mamba_d_head=16,
            mamba_d_state=16, mamba_chunk_size=8, num_attention_heads=4,
            num_key_value_heads=2, attention_multiplier=0.0625,
            num_local_experts=4, num_experts_per_tok=3, intermediate_size=24,
            shared_intermediate_size=48)


def add_granite_tiny(root: Path) -> dict:
    """The real files of the configuration under the name ``granite-tiny``,
    with a configuration of tiny widths and a mix with short blocks: new
    files and appended entries only."""
    bench, m, before = copy_benchmark(root)
    tiny = config()
    tiny.update(TINY, name="granite-tiny")
    tiny["published"].update(num_local_experts=8, vocab_size=512)
    tiny["unit"].update(window=64, stride=64)
    tiny["check_units"] = 2
    tiny["run_keys"].update(
        device="cpu", batch_size=2, stack_size=64, step_size=64,
        max_segments=16, metrics_interval_s=1,
        architecture={**{k: v for k, v in TINY.items()
                         if k not in ("num_local_experts", "vocab_size")},
                      "num_local_experts": 8, "vocab_size": 512,
                      "num_hidden_layers": 10})
    (bench / "configs" / "granite-tiny.json").write_text(json.dumps(tiny))
    for kind in ("costs", "checks", "inputs", "references"):
        shutil.copy(bench / kind / f"{CONFIG}.py",
                    bench / kind / "granite-tiny.py")
    mix = manifest.read_json(bench / "traffic" / "resident-packed-4k.json")
    mix.update(block_s=0.3, trace_s=1.0)
    mix["check_video"]["vocab"] = 256
    (bench / "traffic" / "resident-packed-tiny.json").write_text(
        json.dumps(mix))
    m["configs"].append({
        "name": "granite-tiny", "source": tiny["source"],
        "file": "benchmark/configs/granite-tiny.json",
        "reduced": tiny["reduced"], "why": "a CPU-sized stand-in"})
    m["workloads"].append({
        "name": "granite-tiny-resident", "config": "granite-tiny",
        "traffic": "resident-packed-tiny", "chips": 1,
        "why": "a CPU-sized stand-in"})
    for metric in m["end_to_end"] + m["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("granite-tiny-resident")
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    nothing_edited(bench, before)
    return m


@pytest.fixture
def granite_root(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    add_granite_tiny(root)
    return root


def test_the_cell_joins_the_manifest_with_its_eleven_per_layer_metrics():
    m = manifest.load_manifest(REPO)
    cell = manifest.Cell(m, CELL, REPO)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        CONFIG, "resident-packed-4k", 1)
    assert {e["name"] for e in cell.end_to_end} == {"step_units_per_s",
                                                    "setup_s"}
    names = [p["name"] for p in cell.per_layer]
    assert len(names) == 11 and set(names) == {
        "step.host.cpu_s_per_unit", "step.model.device_s_per_unit",
        "step.model.forward_roofline", "step.model.unscoped_share",
        "step.device.clock_bound_ms", "step.model.ssm_share",
        "step.model.ssd_scan_share", "step.model.moe_share",
        "step.model.attn_share", "step.kernels.ssd_scan_roofline",
        "step.kernels.moe_experts_roofline"}
    assert cell.traffic["driver"] == "resident"
    assert cell.corpus_kind(cell.traffic["check_video"]).SUFFIX == ".tokens"
    for attr in ("features", "control"):
        assert callable(cell.optional_config_function("references", attr))


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_tiny_cell_runs_through_run_py_on_the_cpu(
        granite_root, monkeypatch, capsys, trace):
    on_the_cpu(monkeypatch, granite_root)
    rc = bench_run.main(["--workload", "granite-tiny-resident", "--seed",
                         "2147484001", "--seconds", "3", "--trace", trace],
                        root=granite_root)
    line, out = last_line(capsys)
    assert rc == 0, out
    assert "a full group is (2, 2, 64) int32" in out
    assert "references/granite-tiny.py, handed the timed" in out
    assert line["failed"] == 0 and line["attempted"] > 0
    assert sorted(line["compared"]) == [
        "cosine_min", "largest_expert_load_over_mean",
        "local_assignment_share_off", "relative_error_max",
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
        assert "step.kernels.moe_experts_roofline" not in line["metrics"]
        assert "step.kernels.moe_experts_roofline: nothing to read" in out


def test_the_float8_control_fails_where_the_program_passes(granite_root):
    """``compare()`` on the program's bfloat16 features of the check item,
    then on the reference's float8 control in their place."""
    from vftbench import program
    m = manifest.load_manifest(granite_root)
    cell = manifest.Cell(m, "granite-tiny-resident", granite_root)
    out_dir = granite_root / "benchmark_out"
    (check,) = corpus.build_fixed(
        out_dir, cell.traffic["check_video"],
        [corpus.frames_for(cell.config["check_units"], cell.config["unit"])],
        cell.corpus_kind(cell.traffic["check_video"])).values()
    extractor = program.build_extractor(
        program.program_args(cell.config, out_dir / "run"))
    ran = extractor.extract(check)
    assert cell.config_function("checks", "validate")(
        ran, "granite_hybrid", 2) is None
    import jax
    with jax.default_matmul_precision("highest"):
        reference, control = (
            cell.config_function("references", name)(
                extractor.runner.params, cell.config, check)
            for name in ("features", "control"))
    compare = cell.config_function("checks", "compare")
    passed, failed = (compare(x, reference, "granite_hybrid")
                      for x in (ran, control))
    print("program", passed, "control", failed)
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


def test_seconds_under_a_deeper_scope_and_of_named_operations():
    """``vftbench/scopes.py`` on the hand-built trace of
    ``fixtures/scoped.xspace.textproto`` (its picture is in the file): a
    scope holds what lies below it, an operation that names no scope is
    found by its own name, and where nothing matches the answer is None."""
    import re

    from jax.profiler import ProfileData
    from vftbench import scopes, xspace
    from vftbench.measurement import MOSAIC_OPS, Measurement
    raw = ProfileData.text_proto_to_serialized_xspace(
        (Path(__file__).parent / "fixtures"
         / "scoped.xspace.textproto").read_text())
    m = Measurement()
    m._scope_self_times = xspace.self_times(
        xspace.ops_line(xspace.load_ops(raw)), 1e6, 1.4e6)
    m.trace = {"busy_s": 280e-6, "window_s": 400e-6}
    assert scopes.under(m, "RAFT/update") == pytest.approx(70e-6)
    assert scopes.under(m, "RAFT/update/update_block/encoder/lookup") == \
        pytest.approx(30e-6)
    assert scopes.share(m, "RAFT/update") == pytest.approx(25.0)
    assert scopes.under(m, "GraniteHybrid/mamba/ssd") is None
    assert scopes.share(m, "GraniteHybrid/mamba/ssd") is None
    assert scopes.named(m, re.compile("copy"), xspace.UNSCOPED) == \
        pytest.approx(60e-6)
    assert scopes.named(m, MOSAIC_OPS, xspace.UNSCOPED) is None
    # a measurement without a trace: nothing to read, nothing raised
    assert scopes.under(Measurement(), "RAFT/update") is None
