"""The harness's arithmetic and its manifest, on the CPU and without the
program: seeded plans and schedules, percentiles and window edges, and that
``BENCHMARK.json`` resolves to files by name."""
import json
import re

import numpy as np
import pytest

from vftbench import arrivals, corpus, manifest, stats

from .conftest import BENCH, REPO

VIDEO = manifest.load_module(BENCH / "corpora" / "video.py")

SPEC = {"owner": "backlog-10s", "videos": 16, "width": 320, "height": 240,
        "fps": 25, "codec": "mp4v",
        "duration_s": {"dist": "lognormal", "median": 10.0, "sigma": 0.4,
                       "min": 4.0, "max": 24.0}}


# -- seeded inputs ------------------------------------------------------------

def plan_bytes(seed):
    return json.dumps(corpus.plan(SPEC, seed), sort_keys=True).encode()


def schedule_bytes(seed, mix="poisson-10s"):
    return np.asarray(arrivals.schedule({"rate_rps": 5.0}, 33.0, seed, mix),
                      np.float64).tobytes()


def test_same_seed_same_corpus_plan_other_seed_another():
    assert plan_bytes(7) == plan_bytes(7)
    assert plan_bytes(7) != plan_bytes(8)


def test_every_seed_gets_the_same_amount_of_work():
    frames = [sorted(v["frames"] for v in corpus.plan(SPEC, s))
              for s in (1, 2, 3)]
    assert frames[0] == frames[1] == frames[2]
    assert min(frames[0]) >= 4 * 25 and max(frames[0]) <= 24 * 25
    # the mid-quantiles straddle the median of 10 s
    assert frames[0][7] < 250 < frames[0][8]


def test_same_seed_same_arrival_schedule_other_seed_another():
    assert schedule_bytes(7) == schedule_bytes(7)
    assert schedule_bytes(7) != schedule_bytes(8)
    assert schedule_bytes(7) != schedule_bytes(7, "another-mix")


def test_schedule_has_a_fixed_count_inside_the_horizon():
    for seed in (1, 2, 3):
        due = arrivals.schedule({"rate_rps": 5.0}, 33.0, seed, "m")
        assert len(due) == 165
        assert due == sorted(due) and 0.0 <= due[0] and due[-1] < 33.0
    gaps = np.diff(arrivals.schedule({"rate_rps": 50.0}, 200.0, 1, "m"))
    # exponential gaps: the standard deviation is about the mean
    assert 0.8 < gaps.std() / gaps.mean() < 1.2


def test_bursts_add_arrivals_where_the_mix_says():
    a = {"rate_rps": 2.0,
         "burst": {"rate_rps": 20.0, "period_s": 10.0, "length_s": 1.0}}
    due = np.asarray(arrivals.schedule(a, 100.0, 3, "bursty"))
    assert len(due) == 2 * 100 + 20 * 10
    inside = (np.mod(due, 10.0) < 1.0).sum()
    assert 180 < inside < 260  # 22 a second for 10 s, of 400 in all


def test_units_and_frames_are_inverse():
    clip, pair = {"window": 16, "stride": 16}, {"window": 2, "stride": 1}
    assert [corpus.units_of(n, clip) for n in (15, 16, 31, 32, 355)] == \
        [0, 1, 1, 2, 22]
    assert [corpus.units_of(n, pair) for n in (1, 2, 17, 100)] == \
        [0, 1, 16, 99]
    for unit in (clip, pair):
        for units in (1, 2, 16, 65):
            assert corpus.units_of(corpus.frames_for(units, unit),
                                   unit) == units


def test_synthesised_video_decodes_to_its_plan_and_moves(tmp_path):
    import cv2
    spec = {**SPEC, "videos": 2,
            "duration_s": {"dist": "uniform", "min": 0.4, "max": 0.6}}
    videos = corpus.build(tmp_path, spec, 5, VIDEO)
    again = corpus.build(tmp_path, spec, 5, VIDEO)  # found, not rebuilt
    assert [v["path"] for v in again] == [v["path"] for v in videos]
    for v in videos:
        cap = cv2.VideoCapture(v["path"])
        frames = []
        while True:
            ok, frame = cap.read()
            if not ok:
                break
            frames.append(frame)
        cap.release()
        assert len(frames) == v["frames"]
        assert frames[0].shape == (240, 320, 3)
        assert np.abs(frames[0].astype(int) - frames[-1].astype(int)
                      ).mean() > 2.0
    fixed = corpus.build_fixed(tmp_path, spec, [17, 5], VIDEO)
    assert sorted(fixed) == [5, 17]


# -- arithmetic ---------------------------------------------------------------

@pytest.mark.parametrize("values, q, want", [
    ([], 50, None),
    ([3.0], 90, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    ([4, 1, 3, 2], 50, 2.5),
    ([1, 2, 3, 4, 5], 50, 3.0),
    (list(range(1, 101)), 90, 90.1),
    ([10, 20], 25, 12.5),
])
def test_percentile_on_hand_made_samples(values, q, want):
    got = stats.percentile(values, q)
    assert got == want if want is None else got == pytest.approx(want)
    if values:
        assert got == pytest.approx(float(np.percentile(values, q)))


def test_spread_is_the_quartile_distance_over_the_median():
    assert stats.spread([100, 101, 102, 103, 104]) == pytest.approx(2 / 102)
    assert stats.spread([]) is None
    assert stats.samples_beyond(120, 90) == 12
    assert stats.samples_beyond(100, 95) == 5


def test_window_edges_count_only_what_completed_inside():
    done = [(9.999, 5), (10.0, 7), (15.0, 11), (19.999, 13), (20.0, 17)]
    assert stats.units_in_window(done, 10.0, 20.0) == 7 + 11 + 13
    with pytest.raises(ValueError):
        stats.tapered_rate(done, 20.0, 20.0)


def test_the_slope_rate_does_not_swing_with_where_a_burst_falls():
    # six videos answered at once every 1.8 s, 100 units a burst
    true = 100 / 1.8
    plain, slope = [], []
    for phase in (0.0, 0.45, 0.9, 1.35):
        bursts = [(phase + 1.8 * i, 100) for i in range(40)]
        plain.append(stats.units_in_window(bursts, 5.0, 35.0) / 30.0)
        slope.append(stats.tapered_rate(bursts, 5.0, 35.0))
    assert max(plain) - min(plain) > 0.05 * true   # a burst in seventeen
    assert max(slope) - min(slope) < 0.005 * true
    assert all(abs(r - true) < 0.005 * true for r in slope)
    # steady completions: the two agree
    steady = [(0.05 + 0.1 * i, 10) for i in range(100)]
    assert stats.tapered_rate(steady, 0.0, 10.0) == pytest.approx(100.0,
                                                                  rel=1e-3)
    # the weights integrate to one: a single unit at the middle weighs 1.5/T
    assert stats.tapered_rate([(5.0, 1)], 0.0, 10.0) == pytest.approx(0.15)
    assert stats.tapered_rate([(0.0, 1), (10.0, 1)], 0.0, 10.0) == 0.0


def test_memory_peak_adds_the_programs_temporaries_on_the_fullest_chip():
    from vftbench import device

    class Chip:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    chips = [Chip({"peak_bytes_in_use": 300, "peak_bytes_reserved": 1600}),
             Chip({"peak_bytes_in_use": 500, "peak_bytes_reserved": 0}),
             Chip(None)]
    assert device.memory_peak_bytes(chips) == 1900
    assert device.memory_peak_bytes([]) == 0


def test_readers_of_the_two_rates_and_of_the_limit():
    from vftbench.measurement import Measurement
    read = {n: manifest.load_function(BENCH / "readers" / f"{n}.py", "read")
            for n in ("units_per_s", "step_units_per_s",
                      "request_within_limit")}
    m = Measurement()
    assert all(r(m) is None for r in read.values())  # nothing to read
    # resident: the median block, whatever the blocks' completions add up to
    m.block_rates = [1500.0, 1536.0, 1537.0, 1538.0, 900.0]
    assert read["step_units_per_s"](m) == 1536.0
    # served: the slope over the window, not the plain count
    m = Measurement()
    m.t0, m.t1 = 0.0, 10.0
    m.completions = [(0.05 + 0.1 * i, 10) for i in range(100)]
    assert read["units_per_s"](m) == pytest.approx(100.0, rel=1e-3)
    assert read["step_units_per_s"](m) is None
    # open loop: the share inside the mix's limit; unanswered = drain limit
    m.drain_limit_s, m.latency_limit_s = 30.0, 3.0
    m.requests = [{"due": 1.0, "visible": 2.0}, {"due": 2.0, "visible": 5.0},
                  {"due": 3.0, "visible": 6.5}, {"due": 4.0},
                  {"due": 11.0, "visible": 11.5}]
    assert read["request_within_limit"](m) == pytest.approx(50.0)


def test_wire_batches_counts_the_windows_dispatches_by_padded_rows():
    from vftbench.measurement import Measurement
    m = Measurement()
    m.t0, m.t1 = 10.0, 20.0
    m.dispatches = [(9.0, 96, 128), (10.0, 96, 128), (12.0, 128, 128),
                    (13.0, 12, 16), (20.0, 5, 8)]
    assert m.wire_batches() == {16: 1, 128: 2}
    assert Measurement().wire_batches() == {}


def test_device_seconds_a_unit_leave_out_the_profilers_pause():
    """Ledger, PR 25, r21d-resident: 0.659 and 0.765 ms a clip at one rate,
    because ``stop_trace`` took 0.7 and 7.7 s inside the window."""
    from vftbench.measurement import Measurement
    for paused_s in (0.7, 7.7):
        m = Measurement()
        m.t0, m.t1, m.paused_s = 100.0, 151.0, paused_s
        blocks = int((51.0 - paused_s) / 0.25)  # 384 clips every 0.25 s
        m.dispatches = [(100.0 + 0.25 * i, 384, 384) for i in range(blocks)]
        m.trace = {"busy_s": 3.9904, "window_s": 4.0}
        assert m.dispatched_per_s() == pytest.approx(1536.0, rel=5e-3)
        assert m.device_s_per_unit() * 1536.0 == pytest.approx(0.9976,
                                                               rel=5e-3)
    m.paused_s = 0.0  # the served driver pauses nothing: as before
    assert m.dispatched_per_s() == pytest.approx(blocks * 384 / 51.0)


def test_stage_seconds_are_clipped_to_the_window():
    spans = [(9.0, 2.0), (12.0, 1.0), (19.5, 3.0), (30.0, 1.0)]
    assert stats.clipped_seconds(spans, 10.0, 20.0) == \
        pytest.approx(1.0 + 1.0 + 0.5)


def test_a_failed_or_late_request_counts_as_the_drain_limit():
    due = [9.0, 10.0, 11.0, 12.0, 19.9, 20.0]
    done = [9.5, 10.4, None, 50.0, 20.9, 20.5]
    got = stats.request_latencies(due, done, 10.0, 20.0, limit_s=30.0)
    assert got == pytest.approx([0.4, 30.0, 30.0, 1.0])


# -- the manifest -------------------------------------------------------------

@pytest.fixture(params=["the checkout", "a copy with cells appended"])
def checkout(request):
    """Every manifest test below holds of the real checkout AND of a copy of
    its ``BENCHMARK.json`` and ``benchmark/`` to which a later PR's cells
    were appended (``conftest.add_tiny_cells``: new files and entries, no
    edit): a test that pins the list of cells fails on the second."""
    if request.param == "the checkout":
        return REPO
    return request.getfixturevalue("tiny_root")


def test_manifest_has_the_contract_keys_and_resolves_by_name(checkout):
    m = manifest.load_manifest(checkout)
    assert sorted(m) == sorted(["command", "paths", "run_seconds", "configs",
                                "workloads", "end_to_end", "per_layer"])
    assert manifest.check_manifest(m, checkout) == []
    assert m["command"][-1] == "benchmark/run.py"
    assert m["paths"] == ["benchmark", "tests/bench"]
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert len(json.dumps(m)) < 64 * 1024
    name = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in m[section]:
            assert name.match(entry["name"]), entry["name"]
            assert len(entry.get("why", "")) <= 200
    chips = [w["chips"] for w in m["workloads"]]
    assert set(chips) <= {1, 4}
    # four chips cost four times as much: a quarter of the cells, or one
    assert chips.count(4) <= max(1, len(chips) // 4)
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_cell_resolves_to_its_files_and_its_moves_are_reported(
        checkout):
    m = manifest.load_manifest(checkout)
    sources = {"device_trace", "program_span", "program_counter",
               "host_clock"}
    for w in m["workloads"]:
        cell = manifest.Cell(m, w["name"], checkout)
        assert cell.config["name"] == w["config"]
        assert (checkout / "benchmark" / "traffic"
                / f"{w['traffic']}.json").is_file()
        assert callable(cell.config_function("costs", "per_unit"))
        assert callable(cell.config_function("checks", "compare"))
        assert callable(cell.config_function("checks", "validate"))
        reported = {e["name"] for e in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer, w["name"]
        for e in cell.end_to_end:
            assert e["source"] in ("host_clock", "device_trace")
            assert 0.01 <= e["bound"] <= 0.1
            assert callable(cell.reader(e["name"]))
        for p in cell.per_layer:
            assert p["moves"] in reported
            assert p["source"] in sources
            assert callable(cell.reader(p["name"]))
    for c in m["configs"]:
        config = json.loads((checkout / c["file"]).read_text())
        assert config["name"] == c["name"]
        assert config["reduced"] == c["reduced"]
        assert c["file"].startswith("benchmark/")


def test_a_tagged_metric_is_read_by_the_file_of_the_name_it_tags(checkout):
    m = manifest.load_manifest(checkout)
    cell = manifest.Cell(m, "r21d-resident", checkout)
    names = {p["name"]: p for p in cell.per_layer}
    # what the ledger's lines hold for this cell stays; a PR may add to it
    assert {"step.model.forward_roofline", "step.model.device_s_per_unit",
            "step.host.cpu_s_per_unit"} <= set(names)
    assert all(p["moves"] == "step_units_per_s" for p in names.values())
    assert not (cell.bench / "readers"
                / "step.model.forward_roofline.py").exists()
    tagged = cell.reader("step.model.forward_roofline")
    plain = cell.reader("model.forward_roofline")
    assert tagged.__code__.co_filename == plain.__code__.co_filename
    with pytest.raises(manifest.ManifestError):
        cell.reader("step.no.such_reader")
    with pytest.raises(manifest.ManifestError):
        cell.reader("nodots")


def test_the_cells_and_the_kept_mixes_say_what_perf_md_says(checkout):
    m = manifest.load_manifest(checkout)
    # the two cells PERF.md section 4 describes are there and are what it
    # says; what else the list holds is its own PR's to describe
    assert {"r21d-resident", "raft-files"} <= {w["name"]
                                               for w in m["workloads"]}
    flow = manifest.Cell(m, "raft-files", checkout)
    assert flow.traffic_name == "backlog-10s"
    assert flow.config["run_keys"]["batch_size"] == 128
    assert flow.traffic["ramp_s"] == 3.0  # and the window opens then
    frames = sorted(v["frames"] for v in corpus.plan(flow.corpus_spec(), 1))
    assert len(frames) == 16 and sum(frames) == 4305
    pairs = [corpus.units_of(n, flow.config["unit"]) for n in frames]
    assert (min(pairs), max(pairs), sum(pairs)) == (118, 526, 4289)
    step = manifest.Cell(m, "r21d-resident", checkout)
    assert step.config["run_keys"]["clip_batch_size"] == 384
    assert step.traffic["resident_batches"] == 2
    # the mixes kept for a later cell still parse and hold their record
    kept = {n: manifest.read_json(flow.bench / "traffic" / f"{n}.json")
            for n in ("backlog-3s", "poisson-10s")}
    short = {"owner": "backlog-3s", **kept["backlog-3s"]["corpus"]}
    pairs = [corpus.units_of(v["frames"], flow.config["unit"])
             for v in corpus.plan(short, 1)]
    assert (min(pairs), max(pairs)) == (51, 97)
    assert kept["poisson-10s"]["corpus_from"] == "backlog-10s"
    assert kept["poisson-10s"]["latency_limit_s"] == 3.0
    clips = sum(corpus.units_of(n, step.config["unit"]) for n in frames)
    # the rate is 0.8 x the r21d-files median over the clips a video holds
    assert clips == 263 and round(0.8 * 54.98 / (clips / 16), 1) == \
        kept["poisson-10s"]["arrivals"]["rate_rps"] == 2.7


def test_a_config_used_by_no_cell_and_a_dangling_moves_are_found(checkout):
    m = manifest.load_manifest(checkout)
    m["configs"].append({**m["configs"][0], "name": "orphan"})
    m["per_layer"].append({**m["per_layer"][0], "name": "x.y",
                           "moves": "no_such_metric"})
    problems = manifest.check_manifest(m, checkout)
    assert any("orphan" in p for p in problems)
    assert any("no_such_metric" in p for p in problems)


def test_adding_a_cell_adds_files_and_edits_none(tiny_root):
    """A configuration, two traffic mixes, three cells and a per-layer metric
    arrive as new files plus manifest entries (conftest.add_tiny_cells asserts
    no existing file changed); the harness finds every one of them by name.
    tests/bench/test_rehearsal.py then runs one of these cells."""
    m = manifest.load_manifest(tiny_root)
    assert manifest.check_manifest(m, tiny_root) == []
    cell = manifest.Cell(m, "tiny-files", tiny_root)
    assert cell.config["run_keys"]["clip_batch_size"] == 1
    assert cell.corpus_spec()["owner"] == "backlog-tiny"
    assert "serve.requests_in_window" in {p["name"] for p in cell.per_layer}
    assert manifest.Cell(m, "tiny-arrivals", tiny_root).corpus_spec()[
        "owner"] == "backlog-tiny"


# -- the seams: chosen by the presence of a file, and without it as before ----

#: recorded from the parent (f8e3ff4) before ``write_video`` moved to
#: ``corpora/video.py``: the directory and sha256 of the one fixed 17-frame
#: video of the kept mixes' geometry, the directory of ``backlog-10s``'
#: corpus under seed 1, and twelve bytes of the ``resident`` stream of seed 5
PARENT = {"fixed_dir": "fixed-3583186d",
          "fixed_sha256": "7f2363a2c413fbe241d77436e127dcbf"
                          "261b81317c7bc15a8a8d3f584f09f264",
          "corpus_dir": "backlog-10s-s1-8d11f81d",
          "resident_bytes": "5847a546f60b6431ee5d2b9a"}


class Timed:
    """Stands for the extractor that was timed."""
    feature_type = "r21d"

    class runner:
        params = {"stem": np.ones((3, 4), np.float32)}


def twin_calls(monkeypatch):
    """Replace the program's config loader and extractor registry; returns
    the list that every build of a twin is recorded in."""
    from vftbench import program
    built = []

    class Twin:
        def __init__(self, args):
            built.append(args)

        def extract(self, path):
            return {"r21d": np.ones((2, 512), np.float32)}

    monkeypatch.setattr(program, "program_args",
                        lambda config, run_dir, overrides=None: overrides)
    monkeypatch.setattr(program, "build_extractor", Twin)
    return built


def check_result():
    return {"extractor": Timed(), "check_video": "f00256.mp4",
            "check_feats": {"r21d": np.ones((2, 512), np.float32)}}


@pytest.mark.parametrize("seam", ["corpora", "inputs", "references"])
def test_without_its_optional_file_a_seam_does_what_the_parent_did(
        seam, tmp_path, monkeypatch):
    import hashlib
    from pathlib import Path

    import run as bench_run
    from vftbench import resident
    m = manifest.load_manifest()
    step, flow = (manifest.Cell(m, n) for n in ("r21d-resident", "raft-files"))
    if seam == "corpora":
        # no block of the benchmark names a kind: all are videos, written by
        # the moved writer to the same names with the same bytes
        assert "kind" not in flow.corpus_spec()
        assert "kind" not in step.traffic["check_video"]
        assert flow.corpus_kind(flow.corpus_spec()).SUFFIX == ".mp4"
        fixed = Path(corpus.build_fixed(
            tmp_path, step.traffic["check_video"], [17],
            step.corpus_kind(step.traffic["check_video"]))[17])
        assert (fixed.parent.name, fixed.name) == (PARENT["fixed_dir"],
                                                   "f00017.mp4")
        assert hashlib.sha256(fixed.read_bytes()).hexdigest() == \
            PARENT["fixed_sha256"]
        assert corpus.corpus_dir(tmp_path, flow.corpus_spec(), 1).name == \
            PARENT["corpus_dir"]
    elif seam == "inputs":
        assert not (BENCH / "inputs").exists()
        assert step.optional_config_function("inputs",
                                             "resident_batch") is None
        got = resident.byte_batch(corpus.stream(5, "resident", "batches"),
                                  (4, 3), np.uint8)
        want = corpus.stream(5, "resident", "batches").integers(
            0, 256, (4, 3), dtype=np.uint8)
        assert got.tobytes() == want.tobytes()
        assert got.tobytes().hex() == PARENT["resident_bytes"]
        wide = resident.byte_batch(corpus.stream(5, "resident", "batches"),
                                   (4, 3), np.float32)
        assert wide.dtype == np.float32 and (wide == want).all()
    else:
        assert not (BENCH / "references").exists()
        built = twin_calls(monkeypatch)
        verdict = bench_run.reference_check(step, check_result(), tmp_path)
        assert built == [step.config["reference_keys"]]
        assert verdict["ok"] and "twin" in verdict["reference"]
        assert "float32" in verdict["reference"]


def test_with_a_references_file_the_timed_extractor_is_what_it_is_given(
        tiny_root, tmp_path, monkeypatch):
    """Of the timed extractor the file is given the parameter tree the
    window ran, and no more: with it the configuration and the check input."""
    import run as bench_run
    root, m = tiny_root, manifest.load_manifest(tiny_root)
    (root / "benchmark" / "references").mkdir()
    (root / "benchmark" / "references" / "r21d-tiny.py").write_text(
        "import numpy as np\n\n\ndef features(params, config, check_path):\n"
        "    assert sorted(params) == ['stem'] and params['stem'].shape == "
        "(3, 4)\n"
        "    assert config['name'] == 'r21d-tiny', config\n"
        "    assert check_path == 'f00256.mp4'\n"
        "    return {'r21d': np.ones((2, 512), np.float32)}\n\n\n"
        "def control(params, config, check_path):\n"
        "    return {'r21d': np.zeros((2, 512), np.float32)}\n")
    built = twin_calls(monkeypatch)
    cell = manifest.Cell(m, "tiny-resident", root)
    assert manifest.check_manifest(m, root) == []
    result = check_result()
    verdict = bench_run.reference_check(cell, result, tmp_path)
    assert built == [] and "extractor" not in result
    assert verdict["ok"] and verdict["reference"].startswith(
        "references/r21d-tiny.py")
    # the same configuration's other cell finds the same file
    assert callable(manifest.Cell(m, "tiny-files", root)
                    .optional_config_function("references", "features"))


@pytest.mark.parametrize("file, text, said", [
    ("traffic/backlog-tiny.json", None, "corpora/wavv.py does not exist"),
    ("corpora/wavv.py", "SUFFIX = '.wav'\n", "has to define SUFFIX, GEOMETRY"),
    ("inputs/r21d-tiny.py", "def resident(rng, shape, dtype):\n    pass\n",
     "defines no function resident_batch()"),
    ("references/r21d-tiny.py", "FEATURES = 1\n",
     "defines no function features()"),
    ("references/r21d-tiny.py",
     "def features(params, config, check_path):\n    pass\n",
     "defines no function control()"),
])
def test_a_seam_that_names_no_file_or_no_function_is_found_without_jax(
        file, text, said, tiny_root):
    root, m = tiny_root, manifest.load_manifest(tiny_root)
    bench = root / "benchmark"
    mix = json.loads((bench / "traffic" / "backlog-tiny.json").read_text())
    mix["corpus"]["kind"] = "wavv"
    (bench / "traffic" / "backlog-tiny.json").write_text(json.dumps(mix))
    if text is not None:
        (bench / file).parent.mkdir(exist_ok=True)
        (bench / file).write_text(text)
    problems = manifest.check_manifest(m, root)
    assert any(said in p for p in problems), problems
