"""``benchmark/run.py`` end to end on the CPU at a tiny size, through the
real ``ServeLoop``: one of the cells that ``conftest.add_tiny_cells`` adds as
new files. The chip check is replaced HERE, in the test; neither the program
nor ``run.py`` has an option that lets a CPU run print device metrics, and the
second test shows what ``run.py`` does off the chip without the patch."""
import json
import os
import subprocess
import sys

import numpy as np
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


def on_the_cpu(monkeypatch, root):
    """The chip check and the trace's loader replaced for one test."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(root / ".cache" / "xla"))
    monkeypatch.setattr(device, "require_chip", cpu_stand_in)
    monkeypatch.setattr(program, "cache_small_programs", lambda: None)
    monkeypatch.setattr(tracing, "load_xplane", stand_in_trace)


def last_line(capsys):
    out = capsys.readouterr().out
    return json.loads(out.strip().splitlines()[-1]), out


def run_cell(root, capsys, cell, seed, seconds, trace):
    """``run.py``'s ``main`` on ``cell``, and once more with a window four
    times as long where no unit was finished inside the first: under six
    test workers a request now and then outlasts a window of a few seconds
    (2 of 7 served runs in one whole run of the tests, PR 26), and a window
    that holds no unit has no rate and no per-unit reading to print."""
    for window in (seconds, 4 * seconds):
        rc = bench_run.main(["--workload", cell, "--seed", str(seed),
                             "--seconds", str(window), "--trace", trace],
                            root=root)
        line, out = last_line(capsys)
        details = json.loads((root / "benchmark_out" / cell
                              / "last_run.json").read_text())
        if rc != 0 or details["units_in_window"] > 0:
            break
    return rc, line, out


def test_tiny_files_cell_through_the_real_serve_loop(tiny_root, monkeypatch,
                                                     capsys):
    on_the_cpu(monkeypatch, tiny_root)
    rc, line, out = run_cell(tiny_root, capsys, "tiny-files", 3, 3, "1")
    assert rc == 0, out
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "compared"]
    assert line["correct"] is True and line["failed"] == 0, out
    # each number the reference check compared, beside its limit
    assert sorted(line["compared"]) == ["cosine_min", "relative_error_max"]
    assert line["compared"]["cosine_min"]["value"] >= \
        line["compared"]["cosine_min"]["limit"]
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
    on_the_cpu(monkeypatch, tiny_root)
    rc = bench_run.main(["--workload", "tiny-resident", "--seed", "3",
                         "--seconds", "2", "--trace", "0"], root=tiny_root)
    line, out = last_line(capsys)
    assert rc == 0, out
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
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


# -- a family whose request is not a video ------------------------------------

def run_wav_cell(root, monkeypatch, capsys, cell, trace="0"):
    on_the_cpu(monkeypatch, root)
    rc, line, out = run_cell(root, capsys, cell, 2147483659, 2, trace)
    assert rc == 0, out
    assert line["correct"] is True and line["failed"] == 0, out
    return line, out


def test_wav_files_cell_serves_wav_requests_and_checks_by_its_own_reference(
        wav_root, monkeypatch, capsys):
    """The seams carry a family the harness was not written for, with the
    program as it is: ``corpora/wav.py`` writes the corpus, ``ServeLoop``
    serves ``.wav`` requests through ``extractors/vggish.py``, and the
    reference is ``references/vggish-tiny.py``, handed the timed parameter
    tree. With that one file deleted the same cell falls back to the
    program's float32 twin and still passes."""
    line, out = run_wav_cell(wav_root, monkeypatch, capsys, "wav-files")
    assert line["attempted"] >= 2
    assert sorted(line["metrics"]) == ["setup_s", "units_per_s"]
    assert line["metrics"]["units_per_s"]["value"] > 0
    assert "reference check against references/vggish-tiny.py, handed the " \
        "timed parameter tree: agrees" in out
    assert "ok   agrees with the reference on the check input" in out
    corpus_dirs = sorted(p.name for p in
                         (wav_root / "benchmark_out" / "corpus").iterdir())
    assert [d.split("-")[0] for d in corpus_dirs] == ["backlog", "fixed"]
    wavs = sorted((wav_root / "benchmark_out" / "corpus").rglob("*.wav"))
    assert len(wavs) == 3 + 1 and not list(
        (wav_root / "benchmark_out").rglob("*.mp4"))
    by_file = line["compared"]

    (wav_root / "benchmark" / "references" / "vggish-tiny.py").unlink()
    line, out = run_wav_cell(wav_root, monkeypatch, capsys, "wav-files")
    assert "reference check against the program's twin at reference_keys " \
        "{\"precision\": \"float32\"}: agrees" in out
    # both references are float32 VGGish on the loader's unrounded weights,
    # one flax and one plain: they read nearby numbers, not the same
    print("compared by file", by_file, "by twin", line["compared"])
    assert line["compared"] != by_file


def test_wav_resident_cell_draws_its_groups_from_the_inputs_file(
        wav_root, monkeypatch, capsys):
    """A resident cell of the same family, traced: its groups are what
    ``inputs/vggish-tiny.py`` draws (log-mel values, not bytes), and the
    resident driver records the program's timeline, so a reader of it finds
    the dispatches' spans."""
    from vftbench import resident

    def no_bytes(rng, shape, dtype):
        raise AssertionError("the default bytes were drawn")

    monkeypatch.setattr(resident, "byte_batch", no_bytes)
    line, out = run_wav_cell(wav_root, monkeypatch, capsys, "wav-resident",
                             trace="1")
    assert "a full group is (4, 96, 64, 1) float32" in out
    assert "references/vggish-tiny.py, handed the timed" in out
    assert {"step.host.cpu_s_per_unit", "step.model.device_s_per_unit"} \
        <= set(line["metrics"])
    # the timeline of a resident cell: the runner's own spans were recorded
    assert "spans and" in out and "dispatches" in out
    assert "recorded nothing" not in out
    assert "start_trace took" in out and "dispatched nothing" in out
    took = line["metrics"]["step.model.device_s_per_unit"]["value"]
    details = json.loads((wav_root / "benchmark_out" / "wav-resident"
                          / "last_run.json").read_text())
    assert took > 0 and details["units_in_window"] > 0


def test_an_answer_altered_where_it_is_produced_comes_out_not_correct(
        wav_root, monkeypatch, capsys):
    """The rest of a run with the timed path broken underneath: every group
    the runner returns is 2% off in half of its rows. The requests are
    answered and their artifacts are sound, so only the comparison with the
    reference can tell, and ``correct`` has to come out false."""
    from video_features_tpu.parallel.mesh import DataParallelApply
    real = DataParallelApply.dispatch

    def altered(self, batch):
        out = real(self, batch)
        return out * (1.0 + 0.02 * (np.arange(out.shape[0]) % 2)
                      )[:, None].astype(out.dtype)

    monkeypatch.setattr(DataParallelApply, "dispatch", altered)
    on_the_cpu(monkeypatch, wav_root)
    rc = bench_run.main(["--workload", "wav-files", "--seed", "5",
                         "--seconds", "2", "--trace", "0"], root=wav_root)
    line, out = last_line(capsys)
    assert rc == 0, out
    assert line["failed"] == 0 and line["attempted"] >= 2, out
    assert line["correct"] is False, out
    assert "FAIL agrees with the reference on the check input" in out
    worst = line["compared"]["relative_error_max"]
    assert worst["value"] > worst["limit"]


def test_the_float8_control_in_the_programs_place_comes_out_not_correct(
        wav_root):
    """The reference file's own control: the same arithmetic with weights
    and every layer's input rounded to float8, the nearest precision under
    the configuration's bfloat16, judged by ``compare()`` as the program's
    features would be. It has to fail, by three times what the bfloat16
    program reads (0.0050-0.0076, ``checks/vggish-tiny.py``) or more; and
    a tree that is not the loader's, rounded once, stops the reference."""
    import jax
    import jax.numpy as jnp
    from vftbench import corpus, manifest
    from video_features_tpu.models.vggish import init_params
    cell = manifest.Cell(manifest.load_manifest(wav_root), "wav-files",
                         wav_root)
    spec = cell.corpus_spec()
    check = corpus.build_fixed(wav_root / "benchmark_out", spec, [
        corpus.frames_for(int(cell.config["check_units"]),
                          cell.config["unit"])], cell.corpus_kind(spec))
    path, = check.values()
    timed = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                                   init_params())
    with jax.default_matmul_precision("highest"):
        reference = cell.optional_config_function(
            "references", "features")(timed, cell.config, path)
        control = cell.optional_config_function(
            "references", "control")(timed, cell.config, path)
        verdict = cell.config_function("checks", "compare")(
            control, reference, "vggish")
        print("float8 control:", verdict)
        assert verdict["ok"] is False
        assert verdict["relative_error_max"] >= 3 * 0.0076
        assert verdict["relative_error_max"] > \
            verdict["bands"]["relative_error_max"]
        timed["features_0"]["kernel"] = timed["features_0"]["kernel"] * 2
        with pytest.raises(AssertionError, match="not the loader's"):
            cell.optional_config_function(
                "references", "features")(timed, cell.config, path)
