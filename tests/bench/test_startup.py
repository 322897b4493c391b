"""What PR 36 adds to the benchmark, on the CPU: the arithmetic the
``setup.*`` readers share (``vftbench/startup.py``) on hand-written snapshots
of the program's start-up ledger, the manifest's seven new entries, and one
traced tiny resident cell in which every one of them reads the live ledger."""
import json
import re

import pytest

import run as bench_run
from vftbench import manifest, startup
from vftbench.measurement import Measurement

from .conftest import REPO, add_tiny_cells
from .test_rehearsal import last_line, on_the_cpu

SETUP_METRICS = ("setup.programs", "setup.cache_misses",
                 "setup.step_programs_s", "setup.other_programs_s",
                 "setup.params_s", "setup.backend_s", "setup.unnamed_share")
CELLS = ["r21d-resident", "raft-files", "granite-h-resident",
         "dsv2-lite-resident"]


def measurement(snap, t0=110.0, setup_s=10.0):
    """A measurement whose set-up is ``[t0 - setup_s, t0]``, holding
    ``snap`` as the ledger's snapshot (what ``startup.snapshot`` caches)."""
    m = Measurement()
    m.t0, m.t1, m.setup_s = t0, t0 + 5.0, setup_s
    m._startup_snapshot = snap
    return m


def read(metric, m):
    return manifest.load_function(
        REPO / "benchmark" / "readers" / f"{metric}.py", "read")(m)


#: the set-up runs 100..110. Phases: backend 101..102, params 103..107,
#: place 107..107.5, the step's first dispatch 108..109.5. Records
#: (stage, name, end, dur, tid): a one-op init program traced, lowered and
#: compiled 104..105.5 INSIDE params; the step traced 108..108.6 with an
#: inner jit's own trace 108.2..108.4 inside it, lowered ..108.9 and loaded
#: ..109.2 (a hit); one program of the harness's 109.6..109.8; and, after the
#: window opened, the reference check's compile, which the cut leaves out
SNAP = {
    "phases": [("backend", 101.0, 1.0, 0.5, {}),
               ("params", 103.0, 4.0, 3.0, {"model_key": "x"}),
               ("place", 107.0, 0.5, 0.1, {}),
               ("first_dispatch", 108.0, 1.5, 1.0, {"padded_rows": 4}),
               ("ready", 107.6, 0.0, 0.0, {})],
    "records": [("trace", "_truncated_normal", 104.5, 0.5, 1),
                ("lower", "_truncated_normal", 105.0, 0.5, 1),
                ("miss", "", 105.1, 0.0, 1),
                ("compile", "_truncated_normal", 105.5, 0.5, 1),
                ("trace", "matmul", 108.4, 0.2, 1),
                ("trace", "vft_tiny_forward", 108.6, 0.6, 1),
                ("lower", "vft_tiny_forward", 108.9, 0.3, 1),
                ("hit", "", 109.0, 0.0, 1),
                ("compile", "vft_tiny_forward", 109.2, 0.3, 1),
                ("trace", "zeros", 109.65, 0.05, 2),
                ("lower", "zeros", 109.7, 0.05, 2),
                ("miss", "", 109.7, 0.0, 2),
                ("compile", "zeros", 109.8, 0.1, 2)],
}


def test_counts_are_of_the_records_the_cut_left():
    m = measurement(SNAP)
    assert read("setup.programs", m) == 3
    assert read("setup.cache_misses", m) == 2


def test_the_vft_split_takes_unions_and_counts_an_inner_trace_once():
    m = measurement(SNAP)
    # the step: 108.0..109.2, the inner matmul's trace inside it once
    assert read("setup.step_programs_s", m) == pytest.approx(1.2)
    # everything else: 104..105.5 and 109.6..109.8; matmul lies in the step's
    assert read("setup.other_programs_s", m) == pytest.approx(1.7)


def test_phases_are_unions_on_the_wall_clock():
    m = measurement(SNAP)
    assert read("setup.params_s", m) == pytest.approx(4.5)
    assert read("setup.backend_s", m) == pytest.approx(1.0)
    # two extractors' params phases that overlap in time count once
    both = dict(SNAP, phases=SNAP["phases"] + [
        ("params", 105.0, 3.0, 1.0, {})])
    assert read("setup.params_s", measurement(both)) == pytest.approx(5.0)


def test_a_compile_inside_a_phase_counts_once_in_the_unnamed_share():
    m = measurement(SNAP)
    # named: 101..102, 103..107.5, 108..109.5, 109.6..109.8 = 7.2 of 10 s
    assert read("setup.unnamed_share", m) == pytest.approx(28.0)


def test_the_set_up_s_own_edges_clip_what_crosses_them():
    # a set-up of 4 s, 106..110: params counts from 106 on, the init
    # program's records (104..105.5) and the backend not at all
    m = measurement(SNAP, setup_s=4.0)
    assert read("setup.params_s", m) == pytest.approx(1.5)
    assert read("setup.backend_s", m) == pytest.approx(0.0)
    assert read("setup.other_programs_s", m) == pytest.approx(0.2)
    assert read("setup.unnamed_share", m) == pytest.approx(
        100.0 * (4.0 - (1.5 + 1.5 + 0.2)) / 4.0)


def test_the_cut_at_the_window_s_first_instant_is_the_ledger_s(monkeypatch):
    """``snapshot`` asks the program's ledger for what ended by ``m.t0``."""
    asked = []

    class Ledger:
        @staticmethod
        def snapshot(until=None):
            asked.append(until)
            return SNAP

    m = Measurement()
    m.t0, m.setup_s = 110.0, 10.0
    monkeypatch.setattr(startup, "program_module", lambda: Ledger)
    assert startup.snapshot(m) is SNAP
    assert startup.snapshot(m) is SNAP  # read once a measurement
    assert asked == [110.0]


@pytest.mark.parametrize("metric", SETUP_METRICS)
def test_without_a_ledger_every_reader_finds_nothing(metric):
    assert read(metric, measurement(None)) is None


def test_a_phase_the_program_never_recorded_is_nothing_to_read():
    bare = {"phases": [], "records": SNAP["records"]}
    assert read("setup.params_s", measurement(bare)) is None
    assert read("setup.backend_s", measurement(bare)) is None
    assert read("setup.programs", measurement(bare)) == 3


def test_the_program_s_ledger_is_found_by_import():
    ledger = startup.program_module()
    assert ledger is not None and callable(ledger.snapshot)


# -- the manifest -------------------------------------------------------------

def test_manifest_is_clean_and_every_new_entry_resolves_to_its_file():
    doc = manifest.load_manifest(REPO)
    assert manifest.check_manifest(doc, REPO) == []
    entries = {e["name"]: e for e in doc["per_layer"]}
    assert [e["name"] for e in doc["per_layer"][-7:]] == list(SETUP_METRICS)
    for name in SETUP_METRICS:
        e = entries[name]
        assert (e["layer"], e["moves"], e["better"]) == \
            ("host", "setup_s", "lower")
        assert e["workloads"] == CELLS
        assert e["source"] == ("program_span" if name in (
            "setup.params_s", "setup.backend_s") else "program_counter")
        assert (REPO / "benchmark" / "readers" / f"{name}.py").is_file()
    for cell in CELLS:
        reported = [e["name"] for e in manifest.Cell(doc, cell, REPO).per_layer]
        assert set(SETUP_METRICS) <= set(reported)


# -- a traced tiny cell reads the live ledger ---------------------------------

def test_a_traced_tiny_resident_cell_reports_all_seven(tmp_path, monkeypatch,
                                                       capsys):
    # an empty ledger: a test worker that has built many extractors before
    # this one may have filled the process-wide one to its caps
    ledger = startup.program_module()
    monkeypatch.setattr(ledger, "_phases", [])
    monkeypatch.setattr(ledger, "_records", [])
    root = tmp_path / "checkout"
    root.mkdir()
    doc = add_tiny_cells(root)
    for e in doc["per_layer"]:
        if e["name"] in SETUP_METRICS:
            e["workloads"].append("tiny-resident")
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    on_the_cpu(monkeypatch, root)
    rc = bench_run.main(["--workload", "tiny-resident", "--seed", "5",
                         "--seconds", "2", "--trace", "1"], root=root)
    line, out = last_line(capsys)
    assert rc == 0, out
    assert line["correct"] is True, out
    got = {name: line["metrics"][name]["value"] for name in SETUP_METRICS}
    assert not [l for l in out.splitlines()
                if "setup." in l and "nothing to read" in l], out
    assert got["setup.programs"] > 0
    assert 0.0 <= got["setup.unnamed_share"] <= 100.0
    # the tiny extractor's step was traced and lowered in this process
    assert got["setup.step_programs_s"] > 0
    # a traced run's line holds no end-to-end metric: set-up is in its log
    setup_s = float(re.search(r"set-up ([0-9.]+) s", out).group(1))
    assert got["setup.step_programs_s"] + got["setup.other_programs_s"] \
        <= setup_s
    assert line["metrics"]["setup.params_s"]["unit"] == "s"
    assert "no program compiled or loaded inside the window" in out
