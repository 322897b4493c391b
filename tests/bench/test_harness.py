"""The harness's arithmetic and its manifest, on the CPU and without the
program: seeded plans and schedules, percentiles and window edges, and that
``BENCHMARK.json`` resolves to files by name."""
import json
import re

import numpy as np
import pytest

from vftbench import arrivals, corpus, manifest, stats

from .conftest import BENCH, REPO

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
    videos = corpus.build(tmp_path, spec, 5)
    again = corpus.build(tmp_path, spec, 5)  # found, not rebuilt
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
    fixed = corpus.build_fixed(tmp_path, spec, [17, 5])
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

def test_manifest_has_the_contract_keys_and_resolves_by_name():
    m = manifest.load_manifest()
    assert sorted(m) == sorted(["command", "paths", "run_seconds", "configs",
                                "workloads", "end_to_end", "per_layer"])
    assert manifest.check_manifest(m) == []
    assert m["command"][-1] == "benchmark/run.py"
    assert m["paths"] == ["benchmark", "tests/bench"]
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert len(json.dumps(m)) < 64 * 1024
    name = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in m[section]:
            assert name.match(entry["name"]), entry["name"]
            assert len(entry.get("why", "")) <= 200
    assert {w["chips"] for w in m["workloads"]} == {1}
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_cell_resolves_to_its_files_and_its_moves_are_reported():
    m = manifest.load_manifest()
    sources = {"device_trace", "program_span", "program_counter",
               "host_clock"}
    for w in m["workloads"]:
        cell = manifest.Cell(m, w["name"])
        assert cell.config["name"] == w["config"]
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
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
        config = json.loads((REPO / c["file"]).read_text())
        assert config["name"] == c["name"]
        assert config["reduced"] == c["reduced"]
        assert c["file"].startswith("benchmark/")


def test_a_tagged_metric_is_read_by_the_file_of_the_name_it_tags():
    m = manifest.load_manifest()
    cell = manifest.Cell(m, "r21d-resident")
    names = {p["name"]: p for p in cell.per_layer}
    assert {"step.model.forward_roofline", "step.model.device_s_per_unit",
            "step.host.cpu_s_per_unit"} == set(names)
    assert all(p["moves"] == "step_units_per_s" for p in names.values())
    assert not (BENCH / "readers" / "step.model.forward_roofline.py").exists()
    tagged = cell.reader("step.model.forward_roofline")
    plain = cell.reader("model.forward_roofline")
    assert tagged.__code__.co_filename == plain.__code__.co_filename
    with pytest.raises(manifest.ManifestError):
        cell.reader("step.no.such_reader")
    with pytest.raises(manifest.ManifestError):
        cell.reader("nodots")


def test_the_cells_and_the_kept_mixes_say_what_perf_md_says():
    m = manifest.load_manifest()
    assert [w["name"] for w in m["workloads"]] == ["r21d-resident",
                                                   "raft-files"]
    flow = manifest.Cell(m, "raft-files")
    assert flow.traffic_name == "backlog-10s"
    assert flow.config["run_keys"]["batch_size"] == 128
    assert flow.traffic["ramp_s"] == 3.0  # and the window opens then
    frames = sorted(v["frames"] for v in corpus.plan(flow.corpus_spec(), 1))
    assert len(frames) == 16 and sum(frames) == 4305
    pairs = [corpus.units_of(n, flow.config["unit"]) for n in frames]
    assert (min(pairs), max(pairs), sum(pairs)) == (118, 526, 4289)
    step = manifest.Cell(m, "r21d-resident")
    assert step.config["run_keys"]["clip_batch_size"] == 384
    assert step.traffic["resident_batches"] == 2
    # the mixes kept for a later cell still parse and hold their record
    kept = {n: manifest.read_json(BENCH / "traffic" / f"{n}.json")
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


def test_a_config_used_by_no_cell_and_a_dangling_moves_are_found():
    m = manifest.load_manifest()
    m["configs"].append({**m["configs"][0], "name": "orphan"})
    m["per_layer"].append({**m["per_layer"][0], "name": "x.y",
                           "moves": "no_such_metric"})
    problems = manifest.check_manifest(m)
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
