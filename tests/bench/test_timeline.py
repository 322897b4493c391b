"""What PR 24 adds to the benchmark, on the CPU: the wire-format reader of a
profiler trace and the program's scopes (on ``fixtures/scoped.xspace
.textproto``, whose picture is in the file), the host timeline's self times,
the clock bracket on made-up lists, gap attribution by the thread that next
enqueues, and one traced tiny cell through the real ``ServeLoop`` in which
every new reader reads the program's own recorder."""
import json
import math
from pathlib import Path

import pytest

import run as bench_run
from vftbench import device, manifest, program, timeline, tracing, xspace
from vftbench.measurement import Measurement

from .test_rehearsal import cpu_stand_in, stand_in_trace

FIXTURE = Path(__file__).parent / "fixtures" / "scoped.xspace.textproto"
US = 1e-6
BASE = 1000 * US  # the fixture's lines start 1 ms into the session


@pytest.fixture(scope="module")
def raw():
    from jax.profiler import ProfileData
    return ProfileData.text_proto_to_serialized_xspace(FIXTURE.read_text())


@pytest.fixture(scope="module")
def selfs(raw):
    ops = xspace.ops_line(xspace.load_ops(raw))
    return xspace.self_times(ops, BASE * 1e9, (BASE + 400 * US) * 1e9)


# -- the trace's wire format and the program's scopes -------------------------

def test_wire_reader_agrees_with_the_profiler_s_own_loader(raw):
    from jax.profiler import ProfileData
    theirs = tracing.load_profile(ProfileData.from_serialized_xspace(raw))
    ours = xspace.load_ops(raw)
    assert sorted(ours) == sorted(theirs) == ["/device:TPU:0"]
    for line, events in theirs["/device:TPU:0"].items():
        assert [(o.name, o.start_ns, o.dur_ns)
                for o in ours["/device:TPU:0"][line]] == events
    # and reads what that loader cannot: the metadata's stats
    lookup = ours["/device:TPU:0"]["XLA Ops"][3]
    assert lookup.op_name.endswith("lookup/jit(_corr_lookup_proj_flat)/"
                                   "pallas_call")
    assert xspace.session_unix_ns(raw) == (1790000000000500000,
                                           1790000000002000000)


@pytest.mark.parametrize("op_name, scope, stage", [
    ("jit(vft_raft_forward)/RAFT/encode/fnet/conv1/conv_general_dilated",
     "RAFT/encode/fnet/conv1", "RAFT/encode"),
    ("jit(vft_raft_forward)/RAFT/update/while/body/closed_call/update_block/"
     "encoder/lookup/jit(_corr_lookup_proj_flat)/pallas_call",
     "RAFT/update/update_block/encoder/lookup", "RAFT/update"),
    # a while's own op_name ends in the scope it sits in, not a primitive
    ("jit(vft_raft_forward)/RAFT/update/while", "RAFT/update",
     "RAFT/update"),
    ("jit(vft_r21d_forward_yuv420)/R2Plus1D/layer1/layer1_0/conv1/conv_t/"
     "conv_general_dilated", "R2Plus1D/layer1/layer1_0/conv1/conv_t",
     "R2Plus1D/layer1"),
    ("jit(<unknown>)/RAFT/while", "RAFT", "unscoped"),  # the parent's
    ("jit(<unknown>)/add", "unscoped", "unscoped"),
    ("", "unscoped", "unscoped"),
])
def test_scope_and_stage_of_an_op_name(op_name, scope, stage):
    assert xspace.scope_of(op_name) == scope
    assert xspace.stage_of(scope) == stage


def test_self_time_per_stage_takes_the_body_out_of_the_while(selfs):
    assert xspace.stage_seconds(selfs) == pytest.approx({
        "RAFT/encode": 50 * US, "RAFT/corr_pyramid": 40 * US,
        "RAFT/update": 70 * US,  # while 20 + lookup 30 + gru 20
        "RAFT/upsample": 60 * US, "unscoped": 60 * US})
    assert sum(ns for _, ns in selfs) / 1e9 == pytest.approx(280 * US)


def test_breakdown_entries_carry_scope_name_and_kind(selfs):
    named = dict(map(tuple, xspace.named_ops(selfs)))
    # one compiler name in two programs and two scopes stays two entries
    assert named["RAFT/encode/fnet/conv1 %fusion.1 fusion"] == \
        pytest.approx(50 * US)
    assert named["RAFT/upsample/bhwkij,bhwkc->bhwijc %fusion.1 fusion"] == \
        pytest.approx(60 * US)
    assert named["RAFT/update/update_block/encoder/lookup "
                 "%_corr_lookup_proj_flat.4 custom-call"] == \
        pytest.approx(30 * US)
    assert named["unscoped %copy.9 copy"] == pytest.approx(60 * US)
    assert len(xspace.named_ops(selfs, top=3)) == 3


def test_self_times_clip_to_the_window(raw):
    ops = xspace.ops_line(xspace.load_ops(raw))
    cut = xspace.self_times(ops, (BASE + 100 * US) * 1e9,
                            (BASE + 160 * US) * 1e9)
    assert xspace.stage_seconds(cut) == pytest.approx({
        "RAFT/update": 50 * US, "RAFT/encode": 10 * US})


# -- the host timeline --------------------------------------------------------

def ev(name, ts, dur, tid=1, sid=None, parent=None, rid="r1", cpu=None,
       **args):
    """One complete event as the program's recorder writes it (us)."""
    return {"ph": "X", "name": name, "ts": ts, "dur": dur, "tid": tid,
            "sid": sid, "parent": parent, "rid": rid,
            "cpu": dur if cpu is None else cpu, "args": args}


def request_tree():
    """A worker (tid 1) and its decode-ahead thread (tid 2), seconds x 1e6:

        serve.request 0..100
          video_attempt 5..95
            prefetch.get_wait 10..30, batch.assemble 30..40,
            mesh.pad 40..45 | mesh.enqueue 45..50, forward 50..80 (fetch
            inside), write 80..90
          (tid 2) prefetch.next 6..28 under video_attempt, decode inside
    """
    return [
        ev("serve.request", 0, 100, sid="0.1", cpu=40),
        ev("video_attempt", 5, 90, sid="0.2", parent="0.1", cpu=38),
        ev("prefetch.get_wait", 10, 20, sid="0.3", parent="0.2", cpu=0),
        ev("batch.assemble", 30, 10, sid="0.4", parent="0.2", cpu=10),
        ev("mesh.pad", 40, 5, sid="0.5", parent="0.2", cpu=5, seq=0, rows=3),
        ev("mesh.enqueue", 45, 5, sid="0.6", parent="0.2", cpu=3, seq=0,
           rows=3, padded_rows=4, program="vft_x"),
        ev("forward", 50, 30, sid="0.7", parent="0.2", cpu=2),
        ev("mesh.fetch", 51, 28, sid="0.8", parent="0.7", cpu=1, seq=0),
        ev("write", 80, 10, sid="0.9", parent="0.2", cpu=8),
        ev("prefetch.next", 6, 22, tid=2, sid="1.1", parent="0.2", cpu=20),
        ev("decode", 7, 20, tid=2, sid="1.2", parent="1.1", cpu=19),
        ev("decode.read", 8, 18, tid=2, sid="1.3", parent="1.2", cpu=18),
        {"ph": "C", "name": "stream.inflight", "ts": 50, "tid": 1,
         "args": {"value": 1}},
    ]


def test_self_time_subtracts_same_thread_children_only():
    t = timeline.Timeline(request_tree(), perf0=100.0)
    selfs = {s.name: (wall, cpu) for s, wall, cpu in
             t.self_times(100.0, 100.0 + 100 * US)}
    # video_attempt 90 less its six children on the worker (80): the
    # decode-ahead thread's prefetch.next hangs under it but runs beside it
    assert selfs["video_attempt"][0] == pytest.approx(10 * US)
    assert selfs["serve.request"][0] == pytest.approx(10 * US)
    assert selfs["forward"] == pytest.approx((2 * US, 1 * US))
    assert selfs["decode"][0] == pytest.approx(2 * US)
    assert t.unnamed_share(100.0, 100.0 + 100 * US) == pytest.approx(20.0)
    # working spans' self cpu: 0+10+5+3+(2-1)+1+8 on the worker, 1+1+18
    assert t.cpu_named(100.0, 100.0 + 100 * US) == pytest.approx(48 * US)
    assert t.seconds(("decode.read", "decode.skip"), 100.0, 200.0) == \
        pytest.approx(18 * US)
    assert t.seconds(("decode.transform",), 100.0, 200.0) is None
    assert t.counters == [("stream.inflight", pytest.approx(100.00005), 1.0)]


def test_a_span_cut_by_the_window_counts_in_proportion():
    t = timeline.Timeline(request_tree(), perf0=0.0)
    # the window closes half way through the write (80..90)
    selfs = {s.name: (wall, cpu) for s, wall, cpu in
             t.self_times(0.0, 85 * US)}
    assert selfs["write"] == pytest.approx((5 * US, 4 * US))
    assert t.dispatches() == [{
        "seq": 0, "tid": 1, "start": pytest.approx(45 * US),
        "end": pytest.approx(50 * US), "at": pytest.approx(40 * US),
        "rows": 3, "padded_rows": 4, "program": "vft_x"}]


# -- idle gaps ----------------------------------------------------------------

def test_a_gap_goes_to_the_leaf_of_the_thread_that_next_enqueues():
    """Two workers. While the device idles 100..160, worker 1 (which
    enqueues next, at 160) is inside batch.collect most of the time; worker
    2 is inside a write that covers ALL of the gap and enqueues later. The
    widest overlap of any thread says write; the device waited for worker
    1's collect."""
    events = [
        ev("serve.request", 0, 400, tid=1, sid="0.1"),
        ev("forward", 90, 20, tid=1, sid="0.2", parent="0.1"),
        ev("batch.collect", 110, 45, tid=1, sid="0.3", parent="0.1"),
        ev("mesh.enqueue", 160, 4, tid=1, sid="0.4", parent="0.1", seq=7,
           rows=8, padded_rows=8),
        ev("serve.request", 0, 400, tid=2, sid="1.1", rid="r2"),
        ev("write", 80, 200, tid=2, sid="1.2", parent="1.1", rid="r2"),
        ev("mesh.enqueue", 300, 4, tid=2, sid="1.3", parent="1.1", rid="r2",
           seq=8, rows=8, padded_rows=8),
    ]
    t = timeline.Timeline(events, perf0=0.0)
    gap = (100 * US, 160 * US)
    assert tracing.attribute(gap, [(s.name, s.start, s.end)
                                   for s in t.spans
                                   if s.name not in timeline.UMBRELLAS]) == \
        "host: write"
    name, leaf = timeline.attribute_gap(gap, t, t.dispatches())
    assert name == "host: batch.collect"
    assert (leaf.tid, leaf.rid) == (1, "r1")
    # inside no span: the umbrella's self time names nothing better
    name, leaf = timeline.attribute_gap((156 * US, 159 * US), t,
                                        t.dispatches())
    assert name == "host: serve.request"
    # nothing was enqueued after the gap
    assert timeline.attribute_gap((350 * US, 380 * US), t,
                                  t.dispatches()) == (tracing.UNKNOWN, None)


# -- the clocks ---------------------------------------------------------------

def made_up_run(offset, host_bound, n=12, seed=3):
    """A device that runs ``n`` programs in enqueue order, on made-up
    clocks ``offset`` apart; a program's length goes with its wire batch.
    ``host_bound``: the device waits for every enqueue (1.5 ms after it
    began) and the host fetches 50 ms late. Else the host keeps two programs
    in flight (it enqueues when the last but one came back) and every fetch
    ends 4 ms after its program: the device never waits."""
    import random
    rng = random.Random(seed)
    enqueues, fetches, modules, free = [], [], [], 0.0
    at = 10.0
    for seq in range(n):
        rows = rng.choice((32, 64, 128))
        if host_bound:
            at += rng.uniform(0.14, 0.6)
        else:
            at = max(at + 0.01, fetches[seq - 2][0] + 0.002
                     if seq >= 2 else 0.0)
        enqueues.append((at, at + 0.001, seq, rows))
        start = max(at + 0.0015, free)
        free = start + 0.004 * rows
        modules.append((start - offset, free - offset, f"jit_x({rows})"))
        fetches.append((free + (0.05 if host_bound else 0.004), seq))
    return modules, enqueues, fetches


@pytest.mark.parametrize("host_bound, width_ms", [(True, 60.0),
                                                  (False, 6.0)])
@pytest.mark.parametrize("first_traced", [0, 3])
def test_clock_bracket_holds_the_offset_and_beats_the_coarse_one(
        host_bound, width_ms, first_traced):
    true = 7.25
    modules, enqueues, fetches = made_up_run(true, host_bound)
    modules = modules[first_traced:first_traced + 6]  # the traced stretch
    coarse = (true - 0.002, true + 0.090)
    lo, hi, shift = timeline.clock_bracket(modules, enqueues, fetches, [],
                                           coarse)
    assert shift == first_traced
    assert coarse[0] <= lo <= true <= hi <= coarse[1]
    assert (hi - lo) * 1e3 < width_ms < (coarse[1] - coarse[0]) * 1e3
    if host_bound:  # the device waited: pinned from below to the enqueue
        assert true - lo == pytest.approx(0.0015)
    else:           # the host waited: pinned from above to the copy's tail
        assert hi - true == pytest.approx(0.004)


def test_clock_bracket_refuses_an_alignment_that_mixes_programs():
    true = 1.0
    modules, enqueues, fetches = made_up_run(true, host_bound=True)
    # the same run, but the trace says every program was another one than
    # the shapes enqueued allow: nothing aligns, the coarse bracket stays
    wrong = [(s, e, f"jit_x({k})") for k, (s, e, _) in enumerate(modules)]
    coarse = (true - 0.002, true + 0.09)
    assert timeline.clock_bracket(wrong[:6], enqueues, fetches, [],
                                  coarse) == (coarse[0], coarse[1], None)


def test_clock_bracket_with_an_open_side_a_hint_and_fences():
    true = 3.0
    modules, enqueues, fetches = made_up_run(true, host_bound=False)
    # no upper bound from the harness (the served driver): the work gives it
    lo, hi, shift = timeline.clock_bracket(
        modules[2:8], enqueues, fetches, [], (true - 0.003, math.inf),
        hint=true + 0.001)
    assert shift == 2 and lo == true - 0.003
    assert hi - true == pytest.approx(0.004)
    # fences in place of fetches (the resident driver): every program
    # enqueued before the fence had finished by then
    fence = modules[5][1] + true + 0.0007
    lo, hi, shift = timeline.clock_bracket(
        modules[2:8], enqueues, [], [fence], (true - 0.003, true + 0.05))
    # enqueues run ahead here, so several alignments satisfy one fence:
    # whichever is taken, the bracket holds the offset
    assert lo <= true <= hi <= true + 0.05


def test_clock_bracket_falls_back_to_the_coarse_one():
    coarse = (1.0, 1.08)
    assert timeline.clock_bracket([], [(1, 2, 0, None)], [], [], coarse) == \
        (1.0, 1.08, None)
    # nothing fits: modules that no enqueue can have caused
    modules = [(0.0, 0.1, None), (0.1, 0.2, None)]
    enqueues = [(50.0, 50.001, 0, None)]
    assert timeline.clock_bracket(modules, enqueues, [], [], coarse) == \
        (1.0, 1.08, None)
    # enqueues that overlap in time have no known order: their fetches
    # bound nothing from above, and the bracket stays open on that side
    modules, enqueues, fetches = made_up_run(2.0, host_bound=False, n=4)
    overlapping = [(s, s + 9.0, seq, rows) for s, _, seq, rows in enqueues]
    lo, hi, _ = timeline.clock_bracket(modules, overlapping, fetches, [],
                                       (1.999, math.inf), hint=2.0)
    assert lo <= 2.0 and hi == math.inf


# -- one analysis per measurement, on the fixture -----------------------------

def fake_measurement(raw, tmp_path):
    """A measurement whose traced sub-window is the fixture's 0..400 us and
    whose harness bracket is 1 ms wide, reduced as ``run.py`` reduces it."""
    path = tmp_path / "benchmark_out" / "cell" / "trace" / "t.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(raw)
    m = Measurement()
    m.trace = tracing.reduce_trace(tracing.load_xplane(path), BASE,
                                   BASE + 400 * US, [], BASE)
    # what TraceWindow keeps on the measurement: the file, and the clock
    # around start_trace (the trace's zero, 50.0 on the host, lies between)
    m.trace_path = path
    m.trace_zero_perf, m.trace_open_perf = 50.0 - 0.2 * BASE, 50.0 + 0.8 * BASE
    m.t0, m.t1 = 50.0 - 0.3 * 600 * US, 50.0 + 700 * US
    m.completions = [(50.0005, 8)]
    m.cpu_s = 0.0005
    return m


def program_side(offset):
    """The program's events for the fixture's three module runs: enqueued
    at 5, 20 and 300 us (trace clock), fetched 1 us after each ended; the
    worker sat in batch.collect through the idle stretch 230..330."""
    def at(us):
        return offset * 1e6 + BASE * 1e6 + us
    return [
        ev("serve.request", at(0), 400, sid="0.1"),
        ev("mesh.enqueue", at(5), 2, sid="0.2", parent="0.1", seq=0, rows=8,
           padded_rows=8),
        ev("mesh.enqueue", at(20), 2, sid="0.3", parent="0.1", seq=1,
           rows=7, padded_rows=8),
        ev("mesh.fetch", at(140), 11, sid="0.4", parent="0.1", seq=0),
        ev("mesh.fetch", at(200), 31, sid="0.5", parent="0.1", seq=1),
        ev("batch.collect", at(232), 90, sid="0.6", parent="0.1"),
        ev("mesh.enqueue", at(325), 2, sid="0.7", parent="0.1", seq=2,
           rows=3, padded_rows=4),
        ev("mesh.fetch", at(380), 11, sid="0.8", parent="0.1", seq=2),
    ]


def test_analysis_renames_the_breakdown_and_ties_the_clocks(
        raw, tmp_path, monkeypatch):
    m = fake_measurement(raw, tmp_path)
    before = [name for name, _ in m.trace["device_ops"]]
    assert "%fusion.1 fusion" in before  # a bare compiler name
    monkeypatch.setattr(timeline, "program_recording", lambda:
                        timeline.Timeline(program_side(50.0), perf0=0.0))
    monkeypatch.setattr(timeline, "last_recorder", lambda: None)
    found = timeline.analysis(m)
    assert timeline.analysis(m) is found  # once per measurement
    device_part = found["device"]
    # the harness's own bracket is the start_trace call, 1 ms; the fetches
    # close it 1 us over the offset, the first enqueue bounds it below
    assert device_part["shift"] == 0
    assert device_part["offset_lo"] <= 50.0 <= device_part["offset_hi"]
    assert device_part["offset_hi"] - 50.0 == pytest.approx(1 * US)
    assert device_part["clock_bound_s"] < 0.001  # the harness's own: 1 ms
    for name, _ in m.trace["device_ops"]:
        assert name.split(" ")[0] == "unscoped" or "/" in name.split(" ")[0]
    assert m.trace["device_ops"][0][0] in (
        "unscoped %copy.9 copy",
        "RAFT/upsample/bhwkij,bhwkc->bhwijc %fusion.1 fusion")
    # the long idle stretch is named after the leaf of the enqueuing thread;
    # every entry keeps the form `host: <span>`
    assert m.trace["idle_gaps"][0] == ["host: batch.collect",
                                       pytest.approx(100 * US)]
    assert all(name.startswith("host: ") for name, _ in m.trace["idle_gaps"])
    assert m.trace["timeline"]["gaps"][0]["rid"] == "r1"
    json.dumps(m.trace["timeline"])  # goes into last_run.json
    assert timeline.stage_share(m, "update") == pytest.approx(25.0)
    assert timeline.stage_share(m, "unscoped") == pytest.approx(100 * 60 / 280)
    assert timeline.stage_share(m, "layer1") == 0.0


def reader(name):
    return manifest.load_function(
        manifest.ROOT / "benchmark" / "readers" / f"{name}.py", "read")


NEW_READERS = [
    "source.read_s_per_unit", "source.transform_s_per_unit",
    "mesh.assemble_s_per_unit", "mesh.collect_s_per_unit",
    "mesh.rows_padded_share", "serve.unnamed_share", "host.cpu_named_share",
    "device.idle_unnamed_share", "device.clock_bound_ms",
    "model.encode_share", "model.corr_pyramid_share", "model.update_share",
    "model.upsample_share", "model.unscoped_share", "model.layer1_share",
    "mesh.group_fill_s_p50", "mesh.ragged_flush_share"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_on_a_program_without_the_recorder_a_new_reader_reads_nothing(
        name, raw, tmp_path, monkeypatch):
    """The driver lays these files over the parent's checkout too: there
    ``trace.last_recording`` does not exist, the reader returns ``None`` and
    ``m.trace`` stays as ``run.py`` reduced it."""
    from video_features_tpu.telemetry import trace
    monkeypatch.delattr(trace, "last_recording")
    m = fake_measurement(raw, tmp_path)
    before = json.dumps(m.trace, sort_keys=True)
    assert reader(name)(m) is None
    assert json.dumps(m.trace, sort_keys=True) == before


def test_a_failing_analysis_leaves_the_line_alone(raw, tmp_path, monkeypatch,
                                                  capsys):
    def boom():
        raise RuntimeError("made up")
    monkeypatch.setattr(timeline, "program_recording", boom)
    m = fake_measurement(raw, tmp_path)
    assert reader("serve.unnamed_share")(m) is None
    assert "the analysis failed" in capsys.readouterr().out


def test_the_manifest_names_each_new_metric_once_and_resolves_it():
    man = manifest.load_manifest()
    assert manifest.check_manifest(man) == []
    names = [e["name"] for e in man["per_layer"]]
    served = manifest.Cell(man, "raft-files")
    mine = [e["name"] for e in served.per_layer]
    for name in NEW_READERS:
        if name not in ("model.layer1_share", "mesh.group_fill_s_p50",
                        "mesh.ragged_flush_share"):
            assert mine.count(name) == 1, name
        served.reader(name)
    # r21d-resident has the entries whose readers PR 24 brought (PR 26: the
    # resident driver records the program's timeline too)
    resident = manifest.Cell(man, "r21d-resident")
    mine = [e["name"] for e in resident.per_layer]
    for name in ("step.device.clock_bound_ms", "step.model.layer1_share",
                 "step.model.unscoped_share"):
        assert mine.count(name) == 1, name
        assert callable(resident.reader(name))
    # every metric the ledger has for PR 22 is still there, in place
    assert names[:14] == [
        "source.decode_s_per_unit", "mesh.stall_s_per_unit",
        "mesh.padding_share", "sinks.write_s_per_unit", "host.cpu_s_per_unit",
        "model.device_s_per_unit", "model.forward_roofline",
        "kernels.custom_call_share", "kernels.corr_lookup_roofline",
        "device.idle_share", "device.peak_hbm_gb", "step.host.cpu_s_per_unit",
        "step.model.device_s_per_unit", "step.model.forward_roofline"]


# -- a traced tiny cell: the readers read the program's own recorder ----------

HOST_SIDE = ["source.read_s_per_unit", "source.transform_s_per_unit",
             "mesh.assemble_s_per_unit", "mesh.collect_s_per_unit",
             "mesh.rows_padded_share", "serve.unnamed_share",
             "host.cpu_named_share", "mesh.group_fill_s_p50",
             "mesh.ragged_flush_share"]


@pytest.fixture(scope="module")
def traced_tiny_cell(tmp_path_factory):
    """``run.py --trace 1`` on the CPU-sized served cell, with this PR's
    host-side metrics joined to it as a later cell would join them."""
    from .conftest import add_tiny_cells
    root = tmp_path_factory.mktemp("checkout")
    man = add_tiny_cells(root)
    for entry in man["per_layer"]:
        if entry["name"] in HOST_SIDE:
            entry["workloads"].append("tiny-files")
    for name, unit, source in (("mesh.group_fill_s_p50", "s", "program_span"),
                               ("mesh.ragged_flush_share", "%",
                                "program_counter")):
        man["per_layer"].append({
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": "mesh", "moves": "units_per_s",
            "workloads": ["tiny-files"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    mp = pytest.MonkeyPatch()
    mp.setenv("JAX_COMPILATION_CACHE_DIR", str(root / ".cache" / "xla"))
    mp.setattr(device, "require_chip", cpu_stand_in)
    mp.setattr(program, "cache_small_programs", lambda: None)
    mp.setattr(tracing, "load_xplane", stand_in_trace)
    import contextlib
    import io
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            rc = bench_run.main(["--workload", "tiny-files", "--seed", "5",
                                 "--seconds", "8", "--trace", "1"], root=root)
    finally:
        mp.undo()
    text = out.getvalue()
    assert rc == 0, text
    if " 0 units, " in text:  # six test workers on eight cores can do that
        pytest.skip("the machine was too busy for one tiny request to "
                    "finish inside the window")
    return json.loads(text.strip().splitlines()[-1]), text


@pytest.mark.parametrize("name", HOST_SIDE)
def test_traced_tiny_cell_prints_the_new_host_side_metric(traced_tiny_cell,
                                                          name):
    line, out = traced_tiny_cell
    assert line["correct"] is True, out
    assert name in line["metrics"], out
    assert line["metrics"][name]["value"] >= 0.0


def test_program_s_padded_rows_equal_the_harness_s_wrapper(traced_tiny_cell):
    line, out = traced_tiny_cell
    assert line["metrics"]["mesh.rows_padded_share"]["value"] == \
        line["metrics"]["mesh.padding_share"]["value"]
    # the split of decode adds up to the stage it splits
    parts = (line["metrics"]["source.read_s_per_unit"]["value"]
             + line["metrics"]["source.transform_s_per_unit"]["value"])
    whole = line["metrics"]["source.decode_s_per_unit"]["value"]
    assert parts <= whole and parts > 0.9 * whole
    assert line["metrics"]["serve.unnamed_share"]["value"] < 10.0, out
    assert 0.0 < line["metrics"]["host.cpu_named_share"]["value"] <= 100.0
    assert "spans and" in out and "from the program's recorder" in out
