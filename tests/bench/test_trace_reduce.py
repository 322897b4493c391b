"""The reduction from a profiler trace to busy time, idle share, per-operation
self time and attributed idle gaps, on a hand-built ``XSpace`` (see
``fixtures/tiny.xspace.textproto`` for its picture) and on hand-made lists.
All times are seconds (or ns) from the start of the profiler's session."""
from pathlib import Path

import pytest

from vftbench import tracing
from vftbench.measurement import MOSAIC_OPS, Measurement

FIXTURE = Path(__file__).parent / "fixtures" / "tiny.xspace.textproto"
US = 1e-6


@pytest.fixture(scope="module")
def planes():
    from jax.profiler import ProfileData
    return tracing.load_profile(ProfileData.from_text_proto(
        FIXTURE.read_text()))


#: the fixture's lines start 1 ms into the session; the tests read 10..110 us
BASE = 1000 * US
OPEN, CLOSE = BASE + 10 * US, BASE + 110 * US


def test_loader_keeps_the_device_lines_and_nothing_of_the_host(planes):
    assert sorted(planes) == ["/device:TPU:0"]
    assert sorted(planes["/device:TPU:0"]) == ["XLA Modules", "XLA Ops"]
    assert planes["/device:TPU:0"]["XLA Ops"][0] == \
        ("while.1", 1020000.0, 40000.0)


def test_busy_is_a_union_nested_and_overlapping_ops_count_once(planes):
    r = tracing.reduce_trace(planes, OPEN, CLOSE)
    # while 20..60 holds two ops; fusion.3 70..80 and copy.4 75..90 overlap
    assert r["busy_s"] == pytest.approx(60 * US)
    assert r["window_s"] == pytest.approx(100 * US)
    # the modules line is not added on top of the operations line
    assert sum(r["self_s"].values()) == pytest.approx(r["busy_s"])


def test_busy_time_is_clipped_to_the_sub_window(planes):
    r = tracing.reduce_trace(planes, BASE + 30 * US, BASE + 75 * US)
    assert r["busy_s"] == pytest.approx((30 + 5) * US)  # 30..60 and 70..75
    assert r["window_s"] == pytest.approx(45 * US)


def test_self_time_takes_children_out_of_their_parent(planes):
    r = tracing.reduce_trace(planes, OPEN, CLOSE)
    assert r["self_s"] == pytest.approx({
        "while.1": 15 * US, "fusion.1": 10 * US, "custom-call.2": 15 * US,
        "fusion.3": 5 * US, "copy.4": 15 * US})
    assert [name for name, _ in r["device_ops"]][-1] == "fusion.3"
    assert len(r["device_ops"]) == 5


def test_gaps_are_listed_longest_first_and_laid_over_host_spans(planes):
    host = [("decode", BASE + 5 * US, 12 * US),    # covers the gap at 10..20
            ("write", BASE + 62 * US, 5 * US),     # inside the gap at 60..70
            ("forward", BASE + 95 * US, 10 * US),  # inside the gap at 90..110
            ("h2d", BASE + 91 * US, 1 * US)]       # a shorter span in that gap
    r = tracing.reduce_trace(planes, OPEN, CLOSE, host)
    assert r["idle_gaps"] == [["host: forward", pytest.approx(20 * US)],
                              ["host: decode", pytest.approx(10 * US)],
                              ["host: write", pytest.approx(10 * US)]]


def test_a_gap_nothing_covers_or_too_short_for_the_clocks_says_unknown(
        planes):
    host = [("decode", BASE + 5 * US, 12 * US),
            ("forward", BASE + 95 * US, 10 * US)]
    r = tracing.reduce_trace(planes, OPEN, CLOSE, host)
    assert [name for name, _ in r["idle_gaps"]] == \
        ["host: forward", "host: decode", tracing.UNKNOWN]
    # clocks known to 4 us: only a gap of 16 us or more is laid over spans
    r = tracing.reduce_trace(planes, OPEN, CLOSE, host, uncertainty_s=4 * US)
    assert r["clock_uncertainty_s"] == 4 * US
    assert [name for name, _ in r["idle_gaps"]] == \
        ["host: forward", tracing.UNKNOWN, tracing.UNKNOWN]


def test_a_trace_with_no_device_operation_is_refused(planes):
    with pytest.raises(ValueError, match="no /device:TPU"):
        tracing.reduce_trace({}, OPEN, CLOSE)
    with pytest.raises(ValueError, match="no line 'XLA Ops'"):
        tracing.reduce_trace({"/device:TPU:0": {"Steps": []}}, OPEN, CLOSE)
    with pytest.raises(ValueError, match="no operation ran"):
        tracing.reduce_trace({"/device:TPU:0": {"XLA Ops": []}}, OPEN, CLOSE)
    with pytest.raises(ValueError, match="no operation ran"):
        tracing.reduce_trace(planes, BASE + 200 * US, BASE + 300 * US)


@pytest.mark.parametrize("intervals, want", [
    ([], []),
    ([(0, 1)], [(0, 1)]),
    ([(0, 2), (1, 3)], [(0, 3)]),
    ([(0, 10), (2, 3), (4, 5)], [(0, 10)]),
    ([(5, 6), (0, 1), (1, 2)], [(0, 2), (5, 6)]),
    ([(0, 1), (3, 3)], [(0, 1)]),
])
def test_busy_union_on_hand_made_intervals(intervals, want):
    assert tracing.busy_union(intervals) == want


@pytest.mark.parametrize("name, want", [
    ("%fusion.54 = bf16[128,16,56,56,64]{0,4,3,2,1:T(8,128)(2,1)} "
     "fusion(bf16[128]{0} %add_maximum_fusion), kind=kOutput",
     "%fusion.54 fusion"),
    ("%_corr_lookup_proj_flat.4 = f32[1,38400,256]{2,1,0:T(8,128)S(1)} "
     "custom-call(f32[1,38400,1,1]{3,2,1,0} %b), "
     'custom_call_target="tpu_custom_call"',
     "%_corr_lookup_proj_flat.4 custom-call"),
    ("%copy-start = (bf16[1,3]{1,0}, u32[]) copy-start(bf16[1,3]{1,0} %x)",
     "%copy-start copy-start"),
    ("while.1", "while.1"),
])
def test_a_tpu_operation_is_named_by_its_result_and_its_kind(name, want):
    # names as the v5e's trace printed them (my chip run, PR 22)
    assert tracing.short_name(name) == want
    assert bool(MOSAIC_OPS.search(want)) == ("custom-call" in want)


def test_idle_gaps_include_both_edges_of_the_window():
    merged = [(2.0, 3.0), (5.0, 9.0)]
    assert tracing.idle_gaps(merged, 0.0, 10.0) == \
        [(0.0, 2.0), (3.0, 5.0), (9.0, 10.0)]
    assert tracing.idle_gaps(merged, 2.5, 6.0) == [(3.0, 5.0)]
    assert tracing.total(tracing.clip(merged, 2.5, 6.0)) == 0.5 + 1.0


def test_two_chips_average_their_busy_time(planes):
    two = dict(planes)
    two["/device:TPU:1"] = {"XLA Ops": [("fusion.9", 1010000.0, 20000.0)]}
    r = tracing.reduce_trace(two, OPEN, CLOSE, chips=2)
    assert r["busy_s"] == pytest.approx((60 + 20) / 2 * US)


def test_measurement_reads_shares_and_kernel_time_from_the_reduction(planes):
    m = Measurement()
    m.trace = tracing.reduce_trace(planes, OPEN, CLOSE)
    m.t0, m.t1 = 0.0, 2.0
    # 12 units in a window of 2 s; one dispatch before it does not count
    m.dispatches = [(-0.5, 4, 4), (0.1, 3, 4), (1.0, 4, 4), (1.9, 5, 8)]
    assert m.idle_share() == pytest.approx(40.0)
    assert m.op_share(MOSAIC_OPS) == pytest.approx(0.25)
    assert m.dispatched_per_s() == pytest.approx(6.0)
    # busy 60% of the sub-window, six units a second: 0.1 s a unit
    assert m.device_s_per_unit() == pytest.approx(0.1)
    m.costs = {"flops": 197e12 * 0.03, "bytes": 819e9 * 0.01}
    m.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert m.roofline_s_per_unit() == (pytest.approx(0.03), "compute")
    untraced = Measurement()
    assert untraced.idle_share() is None and untraced.op_share(MOSAIC_OPS) \
        is None and untraced.device_s_per_unit() is None
