"""Shared by the benchmark's own tests: the harness on ``sys.path`` and a
tiny copy of the benchmark that a CPU can run.

Nothing here touches a chip, describes a TPU topology or loads libtpu; the
tests run under ``JAX_PLATFORMS=cpu`` like the rest of ``tests/``.
"""
import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmark"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))


def copy_benchmark(root: Path):
    """The real ``benchmark/`` copied under ``root``; returns the copy, the
    real manifest and every file's bytes for :func:`nothing_edited`."""
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = root / "benchmark"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    return bench, json.loads((REPO / "BENCHMARK.json").read_text()), before


def nothing_edited(bench: Path, before: dict) -> None:
    after = {p: p.read_bytes() for p in bench.rglob("*")
             if p.is_file() and p in before}
    assert after == before, "adding a cell edited an existing file"


def add_tiny_cells(root: Path) -> dict:
    """Copy the benchmark to ``root`` and ADD, without editing one existing
    file under ``benchmark/``: a configuration (with its costs and checks), two
    traffic mixes, three cells, an end-to-end metric and six per-layer metrics,
    one of them with a reader of its own.
    Returns the new manifest. This is all a later PR does to bring a cell."""
    bench, manifest, before = copy_benchmark(root)

    config = json.loads((bench / "configs" / "r21d-18.json").read_text())
    config["name"] = "r21d-tiny"
    config["run_keys"].update(device="cpu", clip_batch_size=1, stack_size=2,
                              step_size=2, serve_workers=2,
                              metrics_interval_s=1)
    config["architecture"]["frames"] = 2
    config["unit"] = {"name": "clip", "window": 2, "stride": 2}
    config["check_units"] = 1
    (bench / "configs" / "r21d-tiny.json").write_text(json.dumps(config))
    for kind in ("costs", "checks"):
        shutil.copy(bench / kind / "r21d-18.py", bench / kind / "r21d-tiny.py")

    backlog = json.loads((bench / "traffic" / "backlog-10s.json").read_text())
    backlog["corpus"].update(videos=3, duration_s={
        "dist": "uniform", "min": 0.3, "max": 0.5})
    backlog.update(ramp_s=0.5, drain_s=60.0, trace_s=1.0)
    (bench / "traffic" / "backlog-tiny.json").write_text(json.dumps(backlog))
    poisson = json.loads((bench / "traffic" / "poisson-10s.json").read_text())
    poisson.update(corpus_from="backlog-tiny", arrivals={"rate_rps": 2.0},
                   ramp_s=0.5, drain_s=60.0, trace_s=1.0)
    (bench / "traffic" / "poisson-tiny.json").write_text(json.dumps(poisson))

    (bench / "readers" / "serve.requests_in_window.py").write_text(
        '"""Responses that became visible inside the window."""\n\n\n'
        "def read(m):\n    return float(len(m.responses))\n")

    manifest["configs"].append({
        "name": "r21d-tiny", "source": "https://arxiv.org/abs/1711.11248",
        "file": "benchmark/configs/r21d-tiny.json", "reduced": [],
        "why": "a CPU-sized stand-in for the tests"})
    cells = {"tiny-files": "backlog-tiny", "tiny-arrivals": "poisson-tiny",
             "tiny-resident": "resident"}
    for name, traffic in cells.items():
        manifest["workloads"].append({
            "name": name, "config": "r21d-tiny", "traffic": traffic,
            "chips": 1, "why": "a CPU-sized stand-in for the tests"})
    # a new cell joins the metrics the manifest has by name ...
    joins = {"tiny-files": ("units_per_s", "source.decode_s_per_unit",
                            "host.cpu_s_per_unit", "model.device_s_per_unit",
                            "mesh.padding_share", "device.idle_share"),
             "tiny-resident": ("step_units_per_s", "step.host.cpu_s_per_unit",
                               "step.model.device_s_per_unit",
                               "step.model.forward_roofline")}
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        for cell, names in joins.items():
            if metric["name"] in names:
                metric["workloads"].append(cell)
    # ... and brings entries of its own: an end-to-end metric with its bound
    # and per-layer metrics, each read by the file of its name (five that
    # the benchmark keeps for the open loop, one that the test adds)
    manifest["end_to_end"].append({
        "name": "request_within_limit", "unit": "%", "better": "higher",
        "bound": 0.1, "source": "host_clock", "workloads": ["tiny-arrivals"]})
    open_loop = ("request_within_limit", "tiny-arrivals")
    for name, unit, better, source, layer, (moves, cell) in (
            ("request_s_p50", "s", "lower", "host_clock", "serve", open_loop),
            ("request_s_p90", "s", "lower", "host_clock", "serve", open_loop),
            ("serve.wait_s_p50", "s", "lower", "program_span", "serve",
             open_loop),
            ("serve.service_s_p50", "s", "lower", "program_span", "serve",
             open_loop),
            ("mesh.h2d_s_per_unit", "s", "lower", "program_span", "mesh",
             ("units_per_s", "tiny-files")),
            ("serve.requests_in_window", "requests", "higher",
             "program_counter", "serve", ("units_per_s", "tiny-files"))):
        manifest["per_layer"].append({
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": moves, "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    nothing_edited(bench, before)
    return manifest


WAV_FAMILY = Path(__file__).parent / "fixtures" / "wav-family"


def add_wav_cells(root: Path) -> dict:
    """Copy the benchmark to ``root`` and ADD a family whose request is not a
    video (``fixtures/wav-family``, laid over the copy file for file): the
    ``wav`` corpus kind, the ``vggish-tiny`` configuration with its costs,
    checks, resident inputs and plain reference, two traffic mixes and two
    cells that join the manifest's metrics by name. No existing file of the
    benchmark is edited, no entry of the manifest but the ``workloads`` lists
    the cells append their names to."""
    bench, manifest, before = copy_benchmark(root)
    added = json.loads((WAV_FAMILY / "manifest.json").read_text())
    for path in WAV_FAMILY.rglob("*"):
        target = bench / path.relative_to(WAV_FAMILY)
        if path.is_file() and path.name != "manifest.json":
            assert not target.exists(), target
            target.parent.mkdir(exist_ok=True)
            shutil.copy(path, target)
    manifest["configs"] += added["configs"]
    manifest["workloads"] += added["workloads"]
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        for cell, names in added["joins"].items():
            if metric["name"] in names:
                metric["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    nothing_edited(bench, before)
    return manifest


@pytest.fixture
def wav_root(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    add_wav_cells(root)
    return root


@pytest.fixture
def tiny_root(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    add_tiny_cells(root)
    return root
