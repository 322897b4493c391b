"""``benchmark/run.py`` end to end on the CPU at a tiny size, through the
real ``ServeLoop``: one of the cells that ``conftest.add_tiny_cells`` adds as
new files. The chip check is replaced HERE, in the test; neither the program
nor ``run.py`` has an option that lets a CPU run print device metrics, and the
second test shows what ``run.py`` does off the chip without the patch."""
import json
import os
import subprocess
import sys

import pytest

import run as bench_run
from vftbench import device, program, tracing

from .conftest import REPO


def cpu_stand_in(chips, peaks_file):
    """What ``require_chip`` returns, for the CPU the tests run on."""
    import jax
    peaks = json.loads(peaks_file.read_text())["TPU v5 lite"]
    return {"platform": jax.default_backend(), "kind": "cpu stand-in",
            "count": chips, "devices": jax.local_devices()[:chips],
            "peaks": peaks}


def stand_in_trace(path):
    """The CPU has no TPU plane, and with the host tracer off its trace is
    empty: hand the reduction three operations inside the sub-window (which
    opens some tens of milliseconds into the profiler's session). The real
    loader is exercised on a hand-built XSpace in test_trace_reduce.py."""
    assert path.is_file() and path.name.endswith(".xplane.pb")
    ms = 1e6
    return {"/device:TPU:0": {tracing.OPS_LINE: [
        ("%fusion.1 fusion", 300 * ms, 100 * ms),
        ("%fusion.2 fusion", 450 * ms, 50 * ms),
        ("%fusion.1 fusion", 700 * ms, 100 * ms)]}}


def last_line(capsys):
    out = capsys.readouterr().out
    return json.loads(out.strip().splitlines()[-1]), out


def test_tiny_files_cell_through_the_real_serve_loop(tiny_root, monkeypatch,
                                                     capsys):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tiny_root / ".cache" / "xla"))
    monkeypatch.setattr(device, "require_chip", cpu_stand_in)
    monkeypatch.setattr(program, "cache_small_programs", lambda: None)
    monkeypatch.setattr(tracing, "load_xplane", stand_in_trace)
    rc = bench_run.main(["--workload", "tiny-files", "--seed", "3",
                         "--seconds", "3", "--trace", "1"], root=tiny_root)
    line, out = last_line(capsys)
    assert rc == 0, out
    assert sorted(line) == ["attempted", "breakdown", "correct", "device",
                            "failed", "metrics"]
    assert line["correct"] is True and line["failed"] == 0, out
    assert line["attempted"] >= 2
    # the traced run prints the cell's per-layer metrics, each from a reader
    # found by name; the one the test added as a new file is among them
    assert {"source.decode_s_per_unit", "mesh.h2d_s_per_unit",
            "host.cpu_s_per_unit", "model.device_s_per_unit",
            "device.idle_share", "serve.requests_in_window"} \
        <= set(line["metrics"])
    assert "units_per_s" not in line["metrics"]
    for value in line["metrics"].values():
        assert sorted(value) == ["unit", "value"]
    assert line["device"]["platform"] == "cpu"  # and says so
    assert line["device"]["busy_s"] == pytest.approx(0.25)
    assert 0.9 < line["device"]["window_s"] < 1.5  # trace_s of the tiny mix
    assert line["breakdown"]["device_ops"] == [["%fusion.1 fusion", 0.2],
                                               ["%fusion.2 fusion", 0.05]]
    gaps = line["breakdown"]["idle_gaps"]
    assert 1 <= len(gaps) <= 5 and all(
        name.startswith("host: ") and s > 0 for name, s in gaps)
    assert "no program compiled or loaded inside the window" in out
    details = json.loads((tiny_root / "benchmark_out" / "tiny-files"
                          / "last_run.json").read_text())
    assert details["units_in_window"] > 0
    # nothing of the run is left in the spool or the output directory
    run_dir = tiny_root / "benchmark_out" / "tiny-files" / "run"
    assert not list((run_dir / "out").rglob("q0*.npy"))
    assert not list((run_dir / "links").iterdir())


def test_tiny_resident_cell_reports_the_step_rate(tiny_root, monkeypatch,
                                                  capsys):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tiny_root / ".cache" / "xla"))
    monkeypatch.setattr(device, "require_chip", cpu_stand_in)
    monkeypatch.setattr(program, "cache_small_programs", lambda: None)
    rc = bench_run.main(["--workload", "tiny-resident", "--seed", "3",
                         "--seconds", "2", "--trace", "0"], root=tiny_root)
    line, out = last_line(capsys)
    assert rc == 0, out
    assert sorted(line) == ["attempted", "correct", "device", "failed",
                            "metrics"]
    assert line["correct"] is True, out
    # the resident cell's rate has a name and a bound of its own
    assert sorted(line["metrics"]) == ["setup_s", "step_units_per_s"]
    assert line["metrics"]["step_units_per_s"]["value"] > 0
    assert sorted(line["device"]) == ["count", "kind", "memory_peak_bytes",
                                      "platform"]
    assert "wire batches dispatched in the window" in out
    assert "GB as the window opened" in out


def run_off_chip(cwd, script):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, str(script), "--workload", "raft-files", "--seed",
         "1", "--seconds", "2", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120)


def test_off_the_chip_run_py_fails_and_prints_no_result(tmp_path):
    done = run_off_chip(REPO, REPO / "benchmark" / "run.py")
    assert done.returncode == 3
    assert "measures on a TPU only" in done.stderr
    assert "{" not in done.stdout


def test_beside_a_missing_program_run_py_fails_before_it_touches_jax(
        tiny_root):
    # the checkout the fixture built holds only BENCHMARK.json and benchmark/
    done = run_off_chip(tiny_root, tiny_root / "benchmark" / "run.py")
    assert done.returncode != 0
    assert "video_features_tpu" in done.stderr
    assert "{" not in done.stdout
