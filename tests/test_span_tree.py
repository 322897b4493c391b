"""PR 24: one span tree from claim to response (telemetry/trace.py carries
request, thread, parent and CPU on every event; the hot path is tiled with
spans), listeners that do not unhook each other, and the program's own names
on every device operation (``jax.named_scope`` stages, ``name=`` on the
Pallas kernels, ``vft_<family>_<step>`` on the jitted step) — metadata only:
the optimized HLO is the parent's."""
import hashlib
import json
import re
import threading
import time
from functools import partial
from pathlib import Path

import numpy as np
import pytest

from video_features_tpu.telemetry import trace
from video_features_tpu.telemetry.trace import TraceRecorder
from video_features_tpu.utils import profiling
from video_features_tpu.utils.profiling import profiler

REPO = Path(__file__).resolve().parent.parent
SAMPLE = str(REPO / "tests" / "assets" / "v_synth_sample.mp4")

#: served CPU-sized configurations: the packed clip-stack path and the
#: per-video flow path (with a host resize, so `decode.transform` exists)
SERVED = {
    "r21d": dict(feature_type="r21d", clip_batch_size=8, stack_size=4,
                 step_size=4, precision="bfloat16", ingest="yuv420",
                 cross_video_batching=True),
    "raft": dict(feature_type="raft", batch_size=4, iters=2, side_size=64,
                 resize="host",
                 extraction_total=13),
}
#: what one served request has to leave on the timeline, per path
EXPECTED = {
    "r21d": ["serve.claim", "serve.request", "serve.respond", "video_attempt",
             "source_probe", "prefetch.next", "prefetch.get_wait", "decode",
             "decode.read", "decode.transform", "decode.resize",
             "decode.ingest", "batch.assemble", "packer.lock_wait",
             "packer.stack", "packer.route", "mesh.pad", "h2d",
             "mesh.enqueue", "forward", "mesh.fetch", "batch.collect",
             "write"],
    "raft": ["serve.claim", "serve.request", "serve.respond", "video_attempt",
             "prefetch.next", "prefetch.get_wait", "decode", "decode.read",
             "decode.transform", "batch.assemble", "mesh.pad", "h2d",
             "mesh.enqueue", "forward", "mesh.fetch", "batch.collect",
             "write"],
}
COUNTERS = {"r21d": ["packer.buffered"], "raft": ["stream.inflight"]}
#: roots of a request's tree, and events that are timed from outside
ROOTS = {"serve.request", "serve.claim", "serve.respond"}
EXTERNALLY_TIMED = {"serve.claim", "prefetch.put_blocked"}
#: the written number: of a worker's seconds inside serve.request, the share
#: no span below the umbrellas covers
UNCOVERED_LIMIT = 0.05


def serve_one_request(root: Path, keys: dict):
    """One request for the sample video through a real ``ServeLoop`` with
    ``trace=true``; returns the trace file's document and the rows a wrapper
    around ``runner.dispatch`` saw (what the benchmark's ``watch_dispatch``
    does)."""
    from video_features_tpu import serve
    from video_features_tpu.config import load_config, sanity_check
    keys = dict(keys, device="cpu", allow_random_weights=True,
                on_extraction="save_numpy", trace=True, serve_max_requests=1,
                spool_dir=str(root / "spool"), output_path=str(root / "out"),
                tmp_path=str(root / "tmp"))
    args = load_config(keys["feature_type"], keys)
    sanity_check(args, require_videos=False)
    serve.submit_request(str(root / "spool"), [SAMPLE], request_id="req-1")
    loop = serve.ServeLoop(args, out_root=str(args.output_path))
    runner = loop.extractor.runner
    seen, inner = [], runner.dispatch

    def dispatch(batch):
        seen.append((int(batch.shape[0]),
                     int(runner.bucket_batch_size(int(batch.shape[0])))))
        return inner(batch)

    runner.dispatch = dispatch
    try:
        assert loop.run() == 0
    finally:
        del runner.dispatch
    resp = serve.read_response(str(root / "spool"), "req-1")
    assert resp and resp["status"] == "done", resp
    (path,) = (root / "spool").glob("_trace_*.json")
    return json.loads(path.read_text()), seen


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    cache = {}

    def get(family):
        if family not in cache:
            cache[family] = serve_one_request(
                tmp_path_factory.mktemp(family), SERVED[family])
        return cache[family]
    return get


def spans_of(doc):
    return [e for e in doc["traceEvents"] if e.get("ph") == "X"]


# -- the tree -----------------------------------------------------------------

@pytest.mark.parametrize("family, name", [(f, n) for f in EXPECTED
                                          for n in EXPECTED[f]])
def test_a_served_request_records_the_span(served, family, name):
    doc, _ = served(family)
    assert any(e["name"] == name for e in spans_of(doc)), \
        sorted({e["name"] for e in spans_of(doc)})


@pytest.mark.parametrize("family", sorted(EXPECTED))
def test_every_event_carries_thread_request_parent_and_cpu(served, family):
    doc, _ = served(family)
    spans = spans_of(doc)
    by_sid = {e["sid"]: e for e in spans}
    assert len(by_sid) == len(spans)  # ids are unique across threads
    for e in spans:
        for field in trace.REQUIRED_X_FIELDS + trace.SPAN_TREE_FIELDS:
            assert field in e, (field, e)
        assert e["rid"] == "req-1", e
        if e["name"] in ROOTS:
            assert e["parent"] is None, e
        else:
            assert e["parent"] in by_sid, e  # resolvable to ONE event
        if e["name"] in EXTERNALLY_TIMED and e["cpu"] is None:
            continue
        assert e["cpu"] is not None and e["cpu"] >= 0.0, e
        assert e["cpu"] <= e["dur"] * 1.5 + 2000.0, e  # us; one thread's
    for name in COUNTERS[family]:
        assert any(e.get("ph") == "C" and e["name"] == name
                   for e in doc["traceEvents"]), name


@pytest.mark.parametrize("family", sorted(EXPECTED))
def test_children_lie_inside_their_parents(served, family):
    doc, _ = served(family)
    spans = spans_of(doc)
    by_sid = {e["sid"]: e for e in spans}
    slack = 50.0  # us: two clocks are read at each edge
    across = 0
    for e in spans:
        parent = by_sid.get(e["parent"])
        if parent is None:
            continue
        assert e["ts"] >= parent["ts"] - slack, (e, parent)
        assert e["ts"] + e["dur"] <= parent["ts"] + parent["dur"] + slack, \
            (e, parent)
        across += e["tid"] != parent["tid"]
    # the decode-ahead thread's spans hang under the worker's span
    assert across > 0
    producers = [e for e in spans if e["name"] == "prefetch.next"]
    assert {by_sid[e["parent"]]["name"] for e in producers} == \
        {"video_attempt"}
    assert {by_sid[e["parent"]]["name"] for e in spans
            if e["name"] == "decode.read"} == {"decode"}


@pytest.mark.parametrize("family", sorted(EXPECTED))
def test_spans_tile_the_worker_s_time_inside_the_request(served, family):
    doc, _ = served(family)
    spans = spans_of(doc)
    (request,) = [e for e in spans if e["name"] == "serve.request"]
    uncovered = 0.0
    for umbrella in (e for e in spans
                     if e["name"] in ("serve.request", "video_attempt")):
        kids = [e for e in spans if e["parent"] == umbrella["sid"]
                and e["tid"] == umbrella["tid"]]
        uncovered += umbrella["dur"] - sum(k["dur"] for k in kids)
    assert uncovered / request["dur"] < UNCOVERED_LIMIT, \
        (uncovered, request["dur"])


@pytest.mark.parametrize("family", sorted(EXPECTED))
def test_rows_on_mesh_enqueue_equal_what_a_wrapper_saw(served, family):
    doc, seen = served(family)
    enqueues = sorted((e for e in spans_of(doc)
                       if e["name"] == "mesh.enqueue"),
                      key=lambda e: e["args"]["seq"])
    assert [(e["args"]["rows"], e["args"]["padded_rows"])
            for e in enqueues] == seen
    seqs = [e["args"]["seq"] for e in enqueues]
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
    assert {e["args"]["program"] for e in enqueues} == {
        {"r21d": "vft_r21d_forward_yuv420",
         "raft": "vft_raft_forward"}[family]}
    # every dispatch was waited for once, by its seq
    fetched = sorted(e["args"]["seq"] for e in spans_of(doc)
                     if e["name"] == "mesh.fetch")
    assert fetched == seqs
    pads = {e["args"]["seq"] for e in spans_of(doc)
            if e["name"] == "mesh.pad"}
    assert pads == set(seqs)


def test_known_span_names_hold_every_name_the_hot_path_emits(served):
    names = set()
    for family in EXPECTED:
        names |= {e["name"] for e in spans_of(served(family)[0])}
    stages = set(trace.STAGE_NAMES)
    assert names - stages <= set(trace.KNOWN_SPAN_NAMES), \
        names - stages - set(trace.KNOWN_SPAN_NAMES)
    for family in EXPECTED:
        assert set(EXPECTED[family]) - stages <= set(trace.KNOWN_SPAN_NAMES)


# -- off path, listeners ------------------------------------------------------

def test_off_path_stage_and_span_are_one_shared_noop():
    assert trace.active() is None and not profiler.enabled
    saved = profiler._hook
    profiler.set_hook(None)
    try:
        assert profiler.stage("decode") is profiling.NOOP_STAGE
        assert profiler.stage("write") is profiling.NOOP_STAGE
        assert trace.span("mesh.pad", rows=3) is trace.NOOP_TRACE_SPAN
        with profiler.stage("decode"), trace.span("decode.read"):
            pass
        assert trace.current_span_id() is None
        with trace.adopt("0.1"):
            pass
    finally:
        profiler.set_hook(saved)


@pytest.mark.parametrize("recorder_first", [True, False])
def test_recorder_and_stage_listener_both_get_every_stage(tmp_path,
                                                          recorder_first):
    heard = []

    def listener(name, t0, dt):
        heard.append(name)

    rec = TraceRecorder(str(tmp_path))
    if recorder_first:
        rec.start()
        profiler.set_trace_hook(listener)
    else:
        profiler.set_trace_hook(listener)
        rec.start()
    try:
        for name in ("decode", "forward", "write"):
            with profiler.stage(name):
                pass
        # the one that goes first leaves the other listening
        if recorder_first:
            profiler.set_trace_hook(None)
        else:
            rec.close()
        with profiler.stage("h2d"):
            pass
    finally:
        profiler.set_trace_hook(None)
        rec.close()
    recorded = [e["name"] for e in json.loads(
        (tmp_path / "_trace.json").read_text())["traceEvents"]
        if e.get("ph") == "X"]
    want = ["decode", "forward", "write"]
    assert heard == want + ([] if recorder_first else ["h2d"])
    assert recorded == want + (["h2d"] if recorder_first else [])
    assert profiler._trace_hook is None and profiler._tracer is None
    assert trace.active() is None


def test_a_stage_listener_subscribes_to_the_whole_span_tree():
    """What the benchmark's traced run does (program.collect_stage_spans):
    installing a stage listener starts the recorder in memory, removing it
    stops it, and ``last_recording()`` hands the events over."""
    heard = []
    profiler.set_trace_hook(lambda name, t0, dt: heard.append((name, t0)))
    try:
        rec = trace.active()
        assert rec is not None and rec.trace_path is None
        with trace.span("batch.assemble", rows=2):
            with profiler.stage("decode"):
                time.sleep(0.001)
        trace.counter("packer.buffered", 3)
    finally:
        profiler.set_trace_hook(None)
    assert trace.active() is None
    last = trace.last_recording()
    assert last is rec
    events = last.events()
    assert [e["name"] for e in events] == ["batch.assemble", "decode",
                                           "packer.buffered"]
    outer, inner = events[0], events[1]
    assert inner["parent"] == outer["sid"] and outer["parent"] is None
    # the recorder's clock is the listener's: perf0 + ts is perf_counter
    assert last.perf0 + inner["ts"] / 1e6 == pytest.approx(heard[0][1],
                                                           abs=1e-4)
    # with a trace=true recorder running, a listener starts nothing
    explicit = TraceRecorder(None).start()
    try:
        profiler.set_trace_hook(lambda *a: None)
        assert trace.active() is explicit
        profiler.set_trace_hook(None)
        assert trace.active() is explicit
    finally:
        explicit.close()
    assert trace.last_recording() is explicit


def test_span_ids_stay_unique_when_thread_idents_are_reused():
    rec = TraceRecorder(None).start()
    try:
        def work():
            with trace.span("prefetch.next"):
                pass
        for _ in range(12):  # a dead thread's ident goes to the next one
            t = threading.Thread(target=work)
            t.start()
            t.join()
    finally:
        rec.close()
    events = rec.events()
    assert len({e["sid"] for e in events}) == len(events) == 12


def test_prefetcher_carries_request_and_parent_onto_its_thread():
    from video_features_tpu.telemetry.context import use_request
    from video_features_tpu.utils.io import Prefetcher

    def batches():
        for i in range(3):
            with profiler.stage("decode"):
                yield i

    rec = TraceRecorder(None).start()
    try:
        with use_request("rq-7"), trace.span("video_attempt") as attempt:
            assert trace.current_span_id() == attempt._sid
            assert list(Prefetcher(batches())) == [0, 1, 2]
    finally:
        rec.close()
    events = rec.events()
    (umbrella,) = [e for e in events if e["name"] == "video_attempt"]
    nexts = [e for e in events if e["name"] == "prefetch.next"]
    assert len(nexts) == 4  # three batches and the call that found the end
    assert {e["parent"] for e in nexts} == {umbrella["sid"]}
    assert {e["rid"] for e in events} == {"rq-7"}
    assert {e["tid"] for e in nexts} != {umbrella["tid"]}
    waits = [e for e in events if e["name"] == "prefetch.get_wait"]
    assert len(waits) == 4 and {e["tid"] for e in waits} == \
        {umbrella["tid"]}


def test_trace_capture_traces_the_device_only(monkeypatch, tmp_path):
    """profile_trace_dir= used the profiler's defaults: the host and Python
    tracers, a 590 MB flood that left the device 21-26% idle (PR 22)."""
    import jax
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: calls.append((d, kw)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    with profiling.TraceCapture(str(tmp_path)):
        pass
    ((where, kw),) = calls
    options = kw["profiler_options"]
    assert where == str(tmp_path)
    assert options.host_tracer_level == 0
    assert options.python_tracer_level == 0
    assert options.enable_hlo_proto is False


# -- the device side: the program's names -------------------------------------

def _step(family):
    from video_features_tpu.extractors import (clip, i3d, i3d_flow, pwc, r21d,
                                               raft, resnet, s3d, vggish)
    return {
        "r21d": r21d._device_forward_yuv420, "r21d-rgb": r21d._device_forward,
        "raft": raft._raft_forward, "pwc": pwc._pwc_forward,
        "resnet": resnet._device_forward,
        "resnet-yuv": resnet._device_forward_yuv420,
        "s3d": s3d._device_forward, "i3d": i3d._i3d_forward,
        "i3d-flow": i3d_flow._raft_quantized_flow,
        "clip": clip._encode_image, "vggish": vggish._device_forward,
    }[family]


@pytest.mark.parametrize("family, program", [
    ("r21d", "vft_r21d_forward_yuv420"), ("r21d-rgb", "vft_r21d_forward"),
    ("raft", "vft_raft_forward"), ("pwc", "vft_pwc_forward"),
    ("resnet", "vft_resnet_forward"),
    ("resnet-yuv", "vft_resnet_forward_yuv420"), ("s3d", "vft_s3d_forward"),
    ("i3d", "vft_i3d_forward"),
    ("i3d-flow", "vft_i3d_flow_raft_quantized_flow"),
    ("clip", "vft_clip_encode_image"), ("vggish", "vft_vggish_forward"),
])
def test_every_family_s_step_gets_a_stable_program_name(family, program):
    from video_features_tpu.parallel.mesh import step_program_name
    assert step_program_name(partial(_step(family), None, None)) == program
    assert step_program_name(_step(family)) == program


def test_a_step_from_outside_the_package_keeps_its_own_name():
    from video_features_tpu.parallel.mesh import (DataParallelApply,
                                                  step_program_name)

    def double(params, batch):
        return batch * params

    assert step_program_name(double) == "vft_double"
    runner = DataParallelApply(double, np.float32(2.0), fixed_batch=8)
    assert runner.program == "vft_double"
    np.testing.assert_array_equal(
        runner(np.ones((3, 2), np.float32)), 2 * np.ones((3, 2)))
    assert runner.last_seq == 0
    assert "jit_vft_double" in runner._fn.lower(
        runner.params, np.ones((8, 2), np.float32)).compile().as_text()[:200]


def _runner(family):
    """The family's served runner at a CPU-sized batch, and one wire batch."""
    import jax.numpy as jnp
    from video_features_tpu.parallel.mesh import (DataParallelApply,
                                                  cast_floating, get_mesh)
    one = get_mesh(n_devices=1)  # the suite runs on eight virtual devices
    if family == "r21d":
        from video_features_tpu.extractors import r21d as ex
        from video_features_tpu.models import r21d as m
        model = m.R2Plus1D("r2plus1d_18_16_kinetics")
        params = cast_floating(
            m.init_params("r2plus1d_18_16_kinetics")["backbone"],
            jnp.bfloat16)
        return (DataParallelApply(
            partial(ex._device_forward_yuv420, model, jnp.bfloat16), params,
            mesh=one, fixed_batch=2), np.zeros((2, 16, 18816), np.uint8))
    if family == "raft":
        from video_features_tpu.extractors import raft as ex
        from video_features_tpu.models import raft as m
        model = m.RAFT(iters=2, dtype=jnp.bfloat16)
        params = cast_floating(m.init_params(), jnp.bfloat16)
        return (DataParallelApply(partial(ex._raft_forward, model), params,
                                  mesh=one, fixed_batch=1),
                np.zeros((1, 2, 64, 96, 3), np.uint8))
    from video_features_tpu.extractors import resnet as ex
    from video_features_tpu.models import resnet as m
    model = m.ResNet("resnet18")
    params = m.init_params("resnet18")["backbone"]
    return (DataParallelApply(partial(ex._device_forward, model, jnp.float32),
                              params, mesh=one, fixed_batch=1),
            np.zeros((1, 224, 224, 3), np.uint8))


@pytest.fixture
def default_matmul_precision():
    """A float32 extractor built earlier in the process latches
    ``jax_default_matmul_precision`` (extractors/base.py): lower under the
    default, as the served programs are."""
    import jax
    before = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    yield
    jax.config.update("jax_default_matmul_precision", before)


_LOWERED = {}


@pytest.fixture
def lowered(default_matmul_precision):
    def get(family):
        if family not in _LOWERED:
            runner, batch = _runner(family)
            low = runner._fn.lower(runner.params, batch)
            _LOWERED[family] = (low.as_text(debug_info=True),
                                low.compile().as_text())
        return _LOWERED[family]
    return get


SCOPES = {
    "r21d": ["R2Plus1D/ingest", "R2Plus1D/stem", "R2Plus1D/layer1/layer1_0",
             "R2Plus1D/layer1/layer1_1", "R2Plus1D/layer2/layer2_0",
             "R2Plus1D/layer3/layer3_1", "R2Plus1D/layer4/layer4_1",
             "R2Plus1D/head"],
    "raft": ["RAFT/encode/fnet", "RAFT/encode/cnet", "RAFT/corr_pyramid",
             "RAFT/update/while/body", "update_block/encoder", "lookup",
             "update_block/gru", "update_block/flow_head", "RAFT/upsample"],
    "resnet": ["ResNet/"],
}


@pytest.mark.parametrize("family, scope", [(f, s) for f in SCOPES
                                           for s in SCOPES[f]])
def test_lowered_text_of_the_step_names_the_scope(lowered, family, scope):
    text, compiled = lowered(family)
    program = {"r21d": "vft_r21d_forward_yuv420", "raft": "vft_raft_forward",
               "resnet": "vft_resnet_forward"}[family]
    assert f"jit({program})/" in text
    assert compiled.startswith(f"HloModule jit_{program},")
    assert re.search(rf'jit\({program}\)/[^"]*{re.escape(scope)}', text) or \
        re.search(rf'"[^"]*{re.escape(scope)}[^"]*"', text), scope


@pytest.mark.parametrize("family", ["r21d", "raft"])
def test_no_operation_of_a_served_program_is_left_without_a_stage(lowered,
                                                                  family):
    """Every op_name of the optimized program that comes from the step sits
    under <Module>/<stage>."""
    _, compiled = lowered(family)
    program = {"r21d": "vft_r21d_forward_yuv420",
               "raft": "vft_raft_forward"}[family]
    top = {"r21d": "R2Plus1D", "raft": "RAFT"}[family]
    names = re.findall(rf'op_name="jit\({program}\)/([^"]*)"', compiled)
    assert len(names) > 100
    bare = [n for n in names if not re.match(rf"{top}/[a-z_0-9]+/", n)]
    assert not bare, bare[:10]


def test_corr_pyramid_pools_feature_maps_and_never_the_volume(lowered):
    """The pyramid's levels are correlations against the pooled second
    feature map: the stage's only reduce-windows are the three 2x2 pools of
    a (B, Hl, Wl, 256) map, and none has an output with a dimension of
    P = H/8 * W/8 queries (pooling the all-pairs volume was a third of the
    RAFT step on the chip)."""
    _, compiled = lowered("raft")
    queries = (64 // 8) * (96 // 8)  # _runner("raft")'s geometry
    pools = [tuple(int(d) for d in m.group(1).split(","))
             for m in re.finditer(
                 r"= f32\[([\d,]*)\]\S* reduce-window\([^\n]*"
                 r'op_name="[^"]*RAFT/corr_pyramid/', compiled)]
    assert len(pools) == 3, pools
    for shape in pools:
        assert queries not in shape and shape[-1] == 256, shape


@pytest.mark.parametrize("kernel, name", [
    ("corr_lookup_pallas", "corr_lookup_level"),
    ("corr_lookup_proj", "corr_lookup_proj"),
])
def test_pallas_kernels_carry_their_name(kernel, name):
    import jax
    import jax.numpy as jnp
    from video_features_tpu.kernels import corr_lookup as k
    b, h, w = 1, 16, 24
    pyramid = [jnp.zeros((b, h * w, h >> i, w >> i), jnp.float32)
               for i in range(4)]
    coords = jnp.zeros((b, h, w, 2), jnp.float32)
    if kernel == "corr_lookup_pallas":
        def fn():
            return k.corr_lookup_pallas(pyramid, coords, 4, interpret=True)
    else:
        stacked, meta = k.stack_aligned_pyramid(pyramid)
        assert stacked.shape == (b, h * w, 16, 128)  # one shelf of levels

        def fn():
            return k.corr_lookup_proj(stacked, meta, coords,
                                      jnp.zeros((324, 256), jnp.float32),
                                      jnp.zeros((256,), jnp.float32),
                                      interpret=True)
    assert f"name={name}" in str(jax.make_jaxpr(fn)())


# -- scopes are metadata only -------------------------------------------------

#: sha256 of the optimized CPU program for `_runner`'s configuration, metadata
#: and module name stripped, recorded under this jax: r21d's from 1ceebd0
#: (no PR since has touched that program), raft's from PR 25, which changed
#: the pyramid's operations by design (the asset says so)
PARENT_HLO = json.loads((REPO / "tests" / "assets" /
                         "hlo_parent_pr24.json").read_text())


def stripped(text: str) -> str:
    """Optimized HLO text less what names carry: ``metadata={...}``, the
    module's name and the tables of files and frames the metadata points
    into."""
    text = re.sub(r", metadata=\{[^{}]*\}", "", text)
    text = re.sub(r"^HloModule [^,]+,", "HloModule M,", text)
    return re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                  r"(.+\n)*", "\n", text)


@pytest.mark.parametrize("family", ["r21d", "raft"])
def test_optimized_hlo_is_the_parent_s_but_for_names(lowered, family,
                                                     monkeypatch):
    import contextlib
    import jax
    from jax.experimental import pallas as pl
    _, compiled = lowered(family)
    ours = stripped(compiled)
    assert "op_name" not in ours and "vft_" not in ours

    # the same step with every name this PR adds taken away again (flax's
    # own module scopes stay: the parent had them)
    from video_features_tpu import extractors
    from video_features_tpu.models import raft as raft_model
    stages = {"stem", "layer1", "layer2", "layer3", "layer4", "head",
              "encode", "corr_pyramid", "update", "upsample", "lookup"}
    real_scope = jax.named_scope
    monkeypatch.setattr(jax, "named_scope", lambda name:
                        contextlib.nullcontext() if name in stages
                        else real_scope(name))
    for module in (extractors.r21d, extractors.raft, raft_model):
        monkeypatch.setattr(module, "scope",
                            lambda *names: contextlib.nullcontext())
    real = pl.pallas_call
    monkeypatch.setattr(pl, "pallas_call", lambda *a, name=None, **kw:
                        real(*a, **kw))
    runner, batch = _runner(family)
    theirs = stripped(runner._fn.lower(runner.params,
                                       batch).compile().as_text())
    assert hashlib.sha256(ours.encode()).hexdigest() == \
        hashlib.sha256(theirs.encode()).hexdigest()
    recorded = PARENT_HLO[family]
    if recorded["jax"] == jax.__version__:
        # the text a builder recorded from a commit itself (the asset
        # says which)
        assert len(ours) == recorded["chars"]
        assert hashlib.sha256(ours.encode()).hexdigest() == \
            recorded["sha256"]


# -- the gates and the report keep step ---------------------------------------

from .test_schema_gates import _load_script as _script  # noqa: E402


def _x(name, sid, parent=None, cpu=1.0, ts=0.0, dur=10.0, **extra):
    return dict({"ph": "X", "name": name, "ts": ts, "dur": dur, "pid": 1,
                 "tid": 1, "sid": sid, "parent": parent, "rid": "r",
                 "cpu": cpu}, **extra)


@pytest.mark.parametrize("events, finding", [
    ([_x("decode", "0.1"), _x("decode.read", "0.2", parent="0.1")], None),
    ([_x("decode", "0.1"), _x("decode.read", "0.1")], "not unique"),
    ([_x("decode.read", "0.2", parent="9.9")], "no event's sid"),
    ([_x("decode", "0.1", cpu=-3.0)], "cpu=-3.0"),
    ([_x("decode", "0.1", cpu=None)], None),  # externally timed
    ([_x("my.new.span", "0.1")], "outside KNOWN_SPAN_NAMES"),
    ([_x("decode", "0.1"), {"ph": "C", "name": "my.counter", "ts": 0,
                            "pid": 1, "args": {"value": 1}}],
     "outside KNOWN_COUNTER_NAMES"),
])
def test_trace_gate_holds_the_span_tree_to_its_rules(events, finding):
    errs = _script("check_trace_schema").check_tree(events)
    if finding is None:
        assert errs == []
    else:
        assert len(errs) == 1 and finding in errs[0], errs


def test_trace_gate_requires_the_tree_fields_on_a_new_trace(tmp_path):
    gate = _script("check_trace_schema")
    old_style = {"ph": "X", "name": "decode", "ts": 0, "dur": 1, "pid": 1,
                 "tid": 1}
    (tmp_path / "_trace.json").write_text(json.dumps({
        "traceEvents": [old_style],
        "otherData": {"schema": trace.TRACE_SCHEMA}}))
    errs = gate.check(tmp_path)
    assert any("missing ['sid', 'parent', 'rid', 'cpu']" in e for e in errs)


def test_trace_report_shows_requests_and_still_reads_an_old_trace(served,
                                                                  tmp_path):
    report = _script("trace_report")
    doc, _ = served("r21d")
    lines = report.per_request(report.complete_events(doc["traceEvents"]))
    assert lines[0].split()[0] == "request"
    (row,) = lines[1:]
    assert row.split()[0] == "req-1"
    assert int(row.split()[1]) == len(spans_of(doc))
    assert int(row.split()[2]) >= 3  # loop thread, worker, decode-ahead
    tracks = report.counter_tracks(doc["traceEvents"])
    assert any(line.endswith("packer.buffered") for line in tracks), tracks
    # a trace written before PR 24: no sid, parent, rid or cpu anywhere
    old = [{k: v for k, v in e.items()
            if k not in trace.SPAN_TREE_FIELDS} for e in doc["traceEvents"]]
    path = tmp_path / "_trace.json"
    path.write_text(json.dumps({"traceEvents": old,
                                "otherData": doc["otherData"]}))
    assert "no request ids" in report.per_request(
        report.complete_events(old))[0]
    import subprocess
    import sys
    done = subprocess.run([sys.executable,
                           str(REPO / "scripts" / "trace_report.py"),
                           str(path)], capture_output=True, text=True)
    assert done.returncode == 0, done.stderr
    assert "verdict:" in done.stdout and "== per request ==" in done.stdout
    assert "== counters ==" in done.stdout
