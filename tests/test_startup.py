"""The start-up ledger (telemetry/startup.py): the ledger itself, what the
``jax.monitoring`` listeners leave of a compile, the phases at the seams every
family shares, and what ``_run.json`` and ``vft-serve``'s start line say."""
import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from video_features_tpu.telemetry import recorder, startup, trace

REPO_ROOT = Path(__file__).resolve().parents[1]
STAGES = ("trace", "lower", "compile")


@pytest.fixture
def ledger(monkeypatch):
    """An empty ledger for one test: the lists are the module's own names,
    read at every call, so the listeners write into these too."""
    monkeypatch.setattr(startup, "_phases", [])
    monkeypatch.setattr(startup, "_records", [])
    monkeypatch.setattr(startup, "_dropped", {"phases": 0, "records": 0})
    return startup


def phases_since(n):
    return startup.snapshot()["phases"][n:]


# -- (a) the ledger -----------------------------------------------------------

def test_phases_nest_and_carry_cpu(ledger):
    with ledger.phase("params", model_key="x"):
        with ledger.phase("place"):
            sum(i * i for i in range(200_000))  # CPU the thread spends
    ledger.mark("ready")
    place, params, ready = ledger.snapshot()["phases"]  # appended on exit
    assert (place.name, params.name, ready.name) == ("place", "params",
                                                     "ready")
    assert params.detail == {"model_key": "x"} and place.detail == {}
    assert params.start <= place.start
    assert place.start + place.dur <= params.start + params.dur
    assert 0 < place.cpu <= params.cpu and params.cpu <= params.dur + 0.05
    assert ready.dur == 0.0 and ready.start >= params.start + params.dur


def test_a_phase_that_raises_is_recorded_all_the_same(ledger):
    with pytest.raises(KeyError):
        with ledger.phase("params"):
            raise KeyError("no such checkpoint")
    assert [p.name for p in ledger.snapshot()["phases"]] == ["params"]


def test_the_caps_hold_and_overflow_is_counted(ledger, monkeypatch):
    monkeypatch.setattr(startup, "MAX_PHASES", 3)
    monkeypatch.setattr(startup, "MAX_RECORDS", 4)
    for i in range(5):
        ledger.mark("ready", i=i)
    for i in range(7):
        startup._add_record(startup.Record("compile", f"f{i}", float(i),
                                           0.1, 1))
    snap = ledger.snapshot()
    assert [p.detail["i"] for p in snap["phases"]] == [0, 1, 2]  # first N kept
    assert [r.fun_name for r in snap["records"]] == ["f0", "f1", "f2", "f3"]
    assert snap["dropped"] == {"phases": 2, "records": 3}
    assert ledger.summary(snap)["dropped"] == {"phases": 2, "records": 3}


def test_snapshot_cuts_phases_and_records_at_an_instant(ledger):
    for i, stage in enumerate(("trace", "lower", "miss", "compile", "hit")):
        startup._add_record(startup.Record(stage, "f", 10.0 + i, 0.5, 1))
    startup._add_phase(startup.Phase("backend", 9.0, 0.5, 0.1, {}))
    startup._add_phase(startup.Phase("params", 12.5, 3.0, 0.1, {}))
    startup._add_phase(startup.Phase("place", 16.0, 1.0, 0.1, {}))
    cut = ledger.snapshot(until=13.0)
    assert [r.stage for r in cut["records"]] == ["trace", "lower", "miss",
                                                 "compile"]
    assert [p.name for p in cut["phases"]] == ["backend", "params"]
    assert (cut["cache_hits"], cut["cache_misses"]) == (0, 1)
    whole = ledger.snapshot()
    assert len(whole["records"]) == 5 and len(whole["phases"]) == 3
    assert (whole["cache_hits"], whole["cache_misses"]) == (1, 1)
    assert whole["process_start"] <= time.perf_counter()


def test_threads_recording_at_once_lose_nothing(ledger):
    workers, each = 8, 25  # 200 phases and 200 records: under both caps
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def work(k):
            for i in range(each):
                with ledger.phase("params", k=k, i=i):
                    startup._add_record(startup.Record(
                        "compile", f"f{k}", time.perf_counter(), 0.0, k))

        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    snap = ledger.snapshot()
    assert snap["dropped"] == {"phases": 0, "records": 0}
    assert sorted((p.detail["k"], p.detail["i"]) for p in snap["phases"]) == \
        [(k, i) for k in range(workers) for i in range(each)]
    assert len(snap["records"]) == workers * each


def test_an_outer_trace_replaces_the_inner_ones_it_holds(ledger):
    add = startup._add_record
    add(startup.Record("trace", "eager_op", 9.0, 0.5, 1))     # before: stays
    add(startup.Record("lower", "eager_op", 9.4, 0.4, 1))
    add(startup.Record("trace", "sin", 10.2, 0.1, 1))         # inside outer
    add(startup.Record("trace", "other_thread", 10.3, 0.1, 2))
    add(startup.Record("trace", "matmul", 10.5, 0.2, 1))      # inside outer
    add(startup.Record("trace", "inner", 10.8, 0.1, 1))       # inside outer
    add(startup.Record("trace", "outer", 11.0, 1.0, 1))       # 10.0..11.0
    add(startup.Record("trace", "xor", 11.3, 0.1, 1))         # a helper
    add(startup.Record("lower", "outer", 11.5, 0.5, 1))       # traced in here
    # what another thread's record stands before stays (unions count it once)
    assert [(r.stage, r.fun_name) for r in ledger.snapshot()["records"]] == [
        ("trace", "eager_op"), ("lower", "eager_op"), ("trace", "sin"),
        ("trace", "other_thread"), ("trace", "outer"), ("lower", "outer")]
    assert ledger.snapshot()["dropped"]["records"] == 0


def test_union_counts_nested_and_overlapping_intervals_once():
    assert startup.union_s([]) == 0.0
    assert startup.union_s([(0.0, 2.0), (0.5, 1.0), (1.5, 3.0),
                            (5.0, 6.0)]) == pytest.approx(4.0)


def test_summary_splits_the_steps_from_everything_else():
    snap = {"process_start": 100.0, "dropped": {
        "phases": 0, "records": 0}, "cache_hits": 1, "cache_misses": 1,
        "retrieval_s": 0.25, "saved_s": 1.5,
        "phases": [startup.Phase("params", 103.0, 4.0, 3.0, {}),
                   startup.Phase("params", 105.0, 3.0, 1.0, {}),
                   startup.Phase("ready", 108.5, 0.0, 0.0, {}),
                   startup.Phase("first_dispatch", 109.0, 1.5, 1.0, {
                       "program": "vft_x_forward", "padded_rows": 4})],
        "records": [startup.Record("trace", "_normal", 104.5, 0.5, 1),
                    startup.Record("lower", "_normal", 105.0, 0.5, 1),
                    startup.Record("miss", "", 105.1, 0.0, 1),
                    startup.Record("compile", "_normal", 105.5, 0.5, 1),
                    startup.Record("trace", "matmul", 109.4, 0.2, 1),
                    startup.Record("trace", "vft_x_forward", 109.6, 0.6, 1),
                    startup.Record("lower", "vft_x_forward", 109.9, 0.3, 1),
                    startup.Record("hit", "", 110.0, 0.0, 1),
                    startup.Record("compile", "vft_x_forward", 110.2, 0.3,
                                   1)]}
    s = startup.summary(snap)
    assert s["phases"]["params"] == {"s": 5.0, "cpu_s": 4.0, "calls": 2}
    assert s["ready_s"] == 8.5
    assert s["first_dispatches"] == [["vft_x_forward", 4, 1.5]]
    assert (s["programs"], s["cache_hits"], s["cache_misses"]) == (2, 1, 1)
    assert s["steps"] == {"programs": 1, "trace_s": 0.6, "lower_s": 0.3,
                          "compile_s": 0.3}
    assert s["other"] == {"programs": 1, "trace_s": 0.7, "lower_s": 0.5,
                          "compile_s": 0.5}
    assert s["programs_s"] == pytest.approx(1.5 + 1.2)
    assert (s["cache_retrieval_s"], s["cache_saved_s"]) == (0.25, 1.5)
    assert s["top"][:2] == [["_normal", 1.5], ["vft_x_forward", 1.2]]
    assert startup.ready_line(s) == (
        "ready in 8.5 s (backend 0.0, params 5.0, place 0.0, 2 programs "
        "2.7 s, 1 cache misses)")


# -- (b) the listeners --------------------------------------------------------

def test_a_fresh_jit_leaves_one_record_a_stage_under_its_name(ledger):
    import jax
    import jax.numpy as jnp
    baseline = recorder.compile_cache_baseline()  # installs, as it always did

    def vft_probe(x):
        return jnp.tanh(x) @ x.T + 36.0

    fn, x = jax.jit(vft_probe), jnp.ones((5, 7))
    before = time.perf_counter()
    fn(x).block_until_ready()
    after = time.perf_counter()
    mine = [r for r in ledger.snapshot()["records"]
            if r.fun_name == "vft_probe"]
    assert sorted(r.stage for r in mine) == sorted(STAGES)
    for r in mine:
        assert r.dur > 0 and before <= r.end - r.dur and r.end <= after
        assert r.tid == threading.get_ident()
    by = {r.stage: r for r in mine}
    assert by["trace"].end <= by["lower"].end <= by["compile"].end
    # the inner jits (tanh, matmul) reported their own traces inside the
    # outer one's, just before it: the outer record stands for them
    assert [r.fun_name for r in ledger.snapshot()["records"]
            if r.end >= before and r.stage == "trace"] == ["vft_probe"]
    # the same counts, not a second count: every compile request that used
    # the persistent cache is one hit or one miss, stamped once
    summary = recorder.compile_cache_summary(baseline)
    snap = ledger.snapshot()
    assert summary["hits"] == snap["cache_hits"]
    assert summary["misses"] == snap["cache_misses"]
    assert summary["hits"] + summary["misses"] <= 1
    # a second call of the same shape compiles nothing
    n = len(snap["records"])
    fn(x).block_until_ready()
    assert len(ledger.snapshot()["records"]) == n
    assert recorder.compile_cache_summary(baseline) == summary


def test_importing_the_ledger_imports_no_jax():
    code = ("import sys; from video_features_tpu.telemetry import startup; "
            "assert 'jax' not in sys.modules, 'jax imported'; "
            "assert not startup._installed")
    done = subprocess.run([sys.executable, "-c", code], cwd=REPO_ROOT,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr


# -- (c) the seams ------------------------------------------------------------

def build_video(tmp, sample_video):
    from video_features_tpu.config import load_config, sanity_check
    from video_features_tpu.registry import get_extractor_cls
    args = load_config("resnet", dict(
        model_name="resnet18", device="cpu", batch_size=4,
        extraction_total=4, allow_random_weights=True,
        on_extraction="save_numpy", output_path=str(tmp / "out"),
        tmp_path=str(tmp / "tmp")))
    sanity_check(args, require_videos=False)
    return get_extractor_cls("resnet")(args), sample_video, \
        {"model_key": "resnet18"}


def build_tokens(tmp, sample_video):
    from video_features_tpu.config import load_config, sanity_check
    from video_features_tpu.registry import get_extractor_cls

    from .test_deepseek_v2 import documents, tiny_keys, token_file
    args = load_config("deepseek_v2", tiny_keys(tmp))
    sanity_check(args, require_videos=False)
    (doc,) = documents(4, (60,))
    return get_extractor_cls("deepseek_v2")(args), \
        token_file(tmp / "doc.tokens", doc), {"kinds": "dense,moe"}


@pytest.mark.parametrize("build", [build_video, build_tokens],
                         ids=["video", "tokens"])
def test_building_an_extractor_leaves_the_shared_phases(build, tmp_path,
                                                        sample_video, ledger):
    n = len(startup.snapshot()["phases"])
    extractor, item, params_args = build(tmp_path, sample_video)
    built = phases_since(n)
    names = [p.name for p in built]
    assert names.count("backend") == 1 and names.index("backend") == 0
    assert names.count("params") == 1 and names.count("place") >= 1
    assert "first_dispatch" not in names
    (params,) = [p for p in built if p.name == "params"]
    assert params.detail == params_args
    assert params.dur > 0 and params.cpu > 0

    # one dispatch: one first_dispatch a wire shape, with the step's name
    n = len(startup.snapshot()["phases"])
    extractor.extract(item)
    firsts = [p for p in phases_since(n) if p.name == "first_dispatch"]
    assert len(firsts) == 1
    # (resnet's device resize builds its runner at the first resolution it
    # meets: the step is that runner's, not ``extractor.runner``'s)
    program = firsts[0].detail["program"]
    assert program.startswith("vft_") and firsts[0].detail["padded_rows"] >= 1
    step = [r for r in startup.snapshot()["records"]
            if r.fun_name == program]
    assert sorted(r.stage for r in step) == sorted(STAGES)
    assert all(firsts[0].start <= r.end <= firsts[0].start + firsts[0].dur
               for r in step)
    # the same shape again: a set lookup and nothing else
    n = len(startup.snapshot()["phases"])
    extractor.extract(item)
    assert phases_since(n) == []


def test_first_dispatch_is_a_span_where_a_recorder_runs():
    import jax.numpy as jnp

    from video_features_tpu.parallel.mesh import DataParallelApply
    rec = trace.TraceRecorder(None).start()
    try:
        runner = DataParallelApply(lambda p, x: x * p, jnp.float32(2.0),
                                   fixed_batch=4)
        for _ in range(2):
            np.asarray(runner.dispatch(np.ones((3, 2), np.float32)))
    finally:
        rec.close()
    names = [e["name"] for e in rec.events() if e.get("ph") == "X"]
    assert names.count("startup.place") == 1
    assert names.count("startup.first_dispatch") == 1
    assert names.count("mesh.enqueue") == 2
    by_sid = {e["sid"]: e for e in rec.events() if e.get("ph") == "X"}
    first = [e for e in by_sid.values() if e["name"] == "mesh.enqueue"][0]
    assert by_sid[first["parent"]]["name"] == "startup.first_dispatch"
    assert set(names) <= set(trace.KNOWN_SPAN_NAMES) | set(trace.STAGE_NAMES)


# -- (f) _run.json and the start line -----------------------------------------

def test_run_json_holds_the_start_and_agrees_with_compile_cache(
        tmp_path, sample_video):
    """A CLI run in a process of its own, on an empty persistent cache: the
    manifest's ``startup`` key counts the process, ``compile_cache`` the run,
    and in a CLI run the run is the process."""
    out = tmp_path / "out"
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla"),
           # a miss is counted where the program is then written
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    done = subprocess.run(
        [sys.executable, str(REPO_ROOT / "main.py"), "feature_type=resnet",
         "model_name=resnet18", "device=cpu", "batch_size=4",
         "extraction_total=4", "allow_random_weights=true",
         "on_extraction=save_numpy", f"output_path={out}",
         f"tmp_path={tmp_path}/tmp", f"video_paths={sample_video}",
         "telemetry=true", "trace=true", "metrics_interval_s=60"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=280)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    run_dir = out / "resnet" / "resnet18"
    man = json.loads((run_dir / "_run.json").read_text())
    s = man["startup"]
    assert sorted(s) == sorted([
        "phases", "first_dispatches", "ready_s", "programs", "programs_s",
        "steps", "other",
        "cache_hits", "cache_misses", "cache_retrieval_s", "cache_saved_s",
        "top", "dropped"])
    assert {"backend", "params", "place", "first_dispatch", "ready"} \
        <= set(s["phases"])
    assert s["phases"]["params"]["s"] > 0 and s["ready_s"] > 0
    ((program, padded_rows, seconds),) = s["first_dispatches"]
    assert program.startswith("vft_") and padded_rows == 4 and seconds > 0
    assert s["steps"]["programs"] == 1 and s["other"]["programs"] > 10
    assert s["programs"] == s["steps"]["programs"] + s["other"]["programs"]
    assert len(s["top"]) == 10 and s["top"][0][1] >= s["top"][-1][1]
    assert s["dropped"] == {"phases": 0, "records": 0}
    # the same counts: the run is the process
    assert man["compile_cache"]["hits"] == s["cache_hits"]
    assert man["compile_cache"]["misses"] == s["cache_misses"] > 0
    # the tracer starts once the extractor is built: the first dispatch is on
    # its timeline
    doc = json.loads((run_dir / "_trace.json").read_text())
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert "startup.first_dispatch" in names


def test_vft_serve_s_start_line_accounts_for_its_start(tmp_path, capsys):
    from video_features_tpu import serve
    from video_features_tpu.config import load_config, sanity_check
    args = load_config("resnet", dict(
        model_name="resnet18", device="cpu", batch_size=4,
        extraction_total=4, allow_random_weights=True,
        on_extraction="save_numpy", spool_dir=str(tmp_path / "spool"),
        output_path=str(tmp_path / "out"), tmp_path=str(tmp_path / "tmp"),
        serve_idle_exit_s=0.2, serve_workers=1, metrics_interval_s=60))
    sanity_check(args, require_videos=False)
    loop = serve.ServeLoop(args)
    assert loop.run() == 0
    (line,) = [l for l in capsys.readouterr().out.splitlines()
               if l.startswith("vft-serve: ready")]
    assert "families=resnet workers=1 ready in " in line
    for part in (" s (backend ", ", params ", ", place ", " programs ",
                 " cache misses) (heartbeat "):
        assert part in line, line
