#!/usr/bin/env python
"""Trace quick-gate: a real smoke run with ``trace=true`` must emit a
Perfetto-loadable ``_trace.json`` and fan-out-instrumented heartbeats.

Sibling of ``check_telemetry_schema.py`` (which statically pins the span
record shape): the trace contract is dynamic — the interesting failures
are an instrumentation point silently falling off a refactored hot loop,
or an event missing a field Perfetto's JSON importer requires — so this
gate runs an actual 3-family CPU extraction over the vendored sample and
validates what came out:

  1. ``_trace.json`` parses, has a ``traceEvents`` array, and every
     event carries the per-phase required fields declared in
     ``telemetry/trace.py`` (``REQUIRED_X_FIELDS`` etc. — the emitter
     and this checker read the SAME tuples, so they cannot drift);
  2. the pipeline's load-bearing spans are present: ``decode`` and
     ``forward`` stage spans, one ``video_attempt`` per (video, family),
     a ``fanout.decode_pass``, and the ``vft-fanout-decode`` thread
     lane;
  3. the final heartbeat's ``fanout`` section carries queue-depth
     gauges and blocked/starved counters for every visual family;
  4. ``scripts/trace_report.py`` renders the trace and names a
     bottleneck verdict (exit 0, "verdict:" in stdout).

Exit 0 = all green; exit 1 = violations, each listed. Runs on CPU in
the quick CI tier (~a minute: random weights, tiny frame budgets).
"""
from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path
from typing import List

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

from video_features_tpu.telemetry.trace import (  # noqa: E402
    KNOWN_COUNTER_NAMES, KNOWN_SPAN_NAMES, REQUIRED_C_FIELDS,
    REQUIRED_I_FIELDS, REQUIRED_M_FIELDS, REQUIRED_X_FIELDS,
    SPAN_TREE_FIELDS, STAGE_NAMES, TRACE_FILENAME, TRACE_SCHEMA)

#: 3 visual families (frame-wise + frame-wise + clip-stack), tiny frame
#: budgets — the union-plan fan-out with per-family queues, cheap enough
#: for the quick tier
FAMILIES = ("resnet", "clip", "r21d")
SAMPLE = REPO_ROOT / "tests" / "assets" / "v_synth_sample.mp4"

#: a complete event this program writes also carries the span tree's
#: fields (an older trace, which trace_report.py still reads, does not)
REQUIRED_BY_PH = {"X": REQUIRED_X_FIELDS + SPAN_TREE_FIELDS,
                  "i": REQUIRED_I_FIELDS,
                  "C": REQUIRED_C_FIELDS, "M": REQUIRED_M_FIELDS}


def check_tree(events: List[dict]) -> List[str]:
    """The span tree's own rules: ids are unique, a parent resolves to one
    event, cpu is a non-negative number or null, and every name is one the
    vocabulary in telemetry/trace.py knows (a new span joins
    ``KNOWN_SPAN_NAMES`` in the PR that adds it)."""
    errs: List[str] = []
    xs = [e for e in events if e.get("ph") == "X"]
    sids = [e.get("sid") for e in xs]
    if len(set(sids)) != len(sids):
        errs.append("span ids (sid) are not unique")
    known = set(sids)
    for e in xs:
        if e.get("parent") is not None and e["parent"] not in known:
            errs.append(f"span {e.get('name')!r} names a parent "
                        f"{e['parent']!r} that is no event's sid")
        cpu = e.get("cpu")
        if cpu is not None and not (isinstance(cpu, (int, float))
                                    and cpu >= 0):
            errs.append(f"span {e.get('name')!r} has cpu={cpu!r}")
        if len(errs) > 20:
            return errs + ["... (further tree violations elided)"]
    unknown = ({e.get("name") for e in xs}
               - set(KNOWN_SPAN_NAMES) - set(STAGE_NAMES))
    if unknown:
        errs.append(f"span names outside KNOWN_SPAN_NAMES: "
                    f"{sorted(unknown)}")
    strays = ({e.get("name") for e in events if e.get("ph") == "C"}
              - set(KNOWN_COUNTER_NAMES))
    if strays:
        errs.append(f"counter names outside KNOWN_COUNTER_NAMES: "
                    f"{sorted(strays)}")
    return errs


def run_smoke(out: Path, tmp: Path) -> None:
    from video_features_tpu.cli import main as cli_main
    import contextlib
    with contextlib.redirect_stdout(sys.stderr):
        cli_main([
            f"feature_type={','.join(FAMILIES)}", "device=cpu",
            "allow_random_weights=true", "on_extraction=save_numpy",
            "retry_attempts=1", "video_workers=1",
            "resnet.model_name=resnet18", "resnet.batch_size=8",
            "resnet.extraction_total=6",
            "clip.batch_size=8", "clip.extraction_total=4",
            "r21d.extraction_fps=1", "r21d.stack_size=10",
            "r21d.step_size=10",
            f"output_path={out}", f"tmp_path={tmp}",
            f"video_paths={SAMPLE}",
            "trace=true", "telemetry=true", "metrics_interval_s=60",
        ])


def check(out: Path) -> List[str]:
    errs: List[str] = []
    trace_path = out / TRACE_FILENAME
    if not trace_path.exists():
        return [f"{trace_path} was not written"]
    try:
        doc = json.load(open(trace_path))
    except json.JSONDecodeError as e:
        return [f"{trace_path} is not valid JSON ({e}) — the atomic "
                "finalize contract broke"]
    events = doc.get("traceEvents")
    if not isinstance(events, list) or not events:
        return [f"{trace_path}: no traceEvents array"]
    if doc.get("otherData", {}).get("schema") != TRACE_SCHEMA:
        errs.append(f"otherData.schema != {TRACE_SCHEMA!r}")

    # 1. per-phase required fields (emitter <-> checker share the tuples)
    for i, e in enumerate(events):
        ph = e.get("ph")
        if ph is None:
            errs.append(f"event #{i} has no 'ph' phase: {e}")
            continue
        missing = [k for k in REQUIRED_BY_PH.get(ph, ("ph",))
                   if k not in e]
        if missing:
            errs.append(f"event #{i} (ph={ph}, "
                        f"name={e.get('name')!r}) missing {missing}")
            if len(errs) > 20:
                errs.append("... (further field violations elided)")
                break

    errs += check_tree(events)

    # 2. load-bearing spans and lanes
    names = {e.get("name") for e in events if e.get("ph") == "X"}
    for want in ("decode", "forward", "video_attempt",
                 "fanout.decode_pass", "decode.read", "batch.assemble",
                 "mesh.pad", "mesh.enqueue", "mesh.fetch", "batch.collect"):
        if want not in names:
            errs.append(f"no {want!r} span in the trace — an "
                        "instrumentation point fell off")
    attempts = [e for e in events if e.get("ph") == "X"
                and e.get("name") == "video_attempt"]
    if len(attempts) < len(FAMILIES):
        errs.append(f"{len(attempts)} video_attempt spans < "
                    f"{len(FAMILIES)} (one per family expected)")
    threads = {e.get("args", {}).get("name") for e in events
               if e.get("ph") == "M" and e.get("name") == "thread_name"}
    if not any(str(t).startswith("vft-fanout-decode") for t in threads):
        errs.append("no vft-fanout-decode thread lane (bus decode "
                    "thread metadata missing)")

    # 3. heartbeat fan-out gauges (telemetry/recorder.py fanout_snapshot)
    hbs = glob.glob(str(out / "_heartbeat_*.json"))
    if not hbs:
        errs.append("no heartbeat file written")
    else:
        try:
            hb = json.load(open(hbs[0]))
        except (OSError, json.JSONDecodeError) as e:
            # a torn heartbeat is a finding (the atomic-replace contract
            # broke), not a traceback
            return errs + [f"{hbs[0]} is not valid JSON ({e}) — "
                           "write_json_atomic contract broke"]
        fan = hb.get("fanout")
        if not isinstance(fan, dict):
            errs.append("heartbeat has no 'fanout' section")
        else:
            for key in ("queue_depth", "put_blocked_ms_total",
                        "get_starved_ms_total"):
                if key not in fan:
                    errs.append(f"heartbeat fanout section missing {key!r}")
            fams = set(fan.get("queue_depth", {}))
            if not set(FAMILIES) <= fams:
                errs.append(f"heartbeat queue_depth gauges {sorted(fams)} "
                            f"miss families {sorted(set(FAMILIES) - fams)}")

    # 4. the report names a bottleneck
    p = subprocess.run(
        [sys.executable, str(REPO_ROOT / "scripts" / "trace_report.py"),
         str(out)], capture_output=True, text=True)
    if p.returncode != 0:
        errs.append(f"trace_report.py failed (rc={p.returncode}): "
                    f"{p.stderr[-300:]}")
    elif "verdict:" not in p.stdout:
        errs.append("trace_report.py printed no bottleneck verdict")
    return errs


def main() -> int:
    if not SAMPLE.exists():
        print(f"trace gate SKIP: vendored sample missing at {SAMPLE}")
        return 0
    with tempfile.TemporaryDirectory(prefix="vft_trace_gate_") as td:
        out, tmp = Path(td) / "out", Path(td) / "tmp"
        run_smoke(out, tmp)
        errs = check(out)
    if errs:
        print("trace schema/emitter DRIFT:")
        for e in errs:
            print(f"  - {e}")
        return 1
    print(f"trace gate OK: {','.join(FAMILIES)} smoke run emitted a "
          "valid Chrome trace + fanout heartbeat gauges, and "
          "trace_report.py named the bottleneck")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
