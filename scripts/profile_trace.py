#!/usr/bin/env python
"""Summarize a ``jax.profiler`` trace: per-op device-time table.

Companion to the `profile_trace_dir=` CLI knob (utils/profiling.py
TraceCapture): point it at the capture directory and get the top device ops
without TensorBoard — this is the exact analysis that located both round-2
performance wins (the r21d per-layer breakdown and the RAFT scan's
per-iteration relayout passes).

Usage:
    python main.py feature_type=... profile_trace_dir=/tmp/trace ...
    python scripts/profile_trace.py /tmp/trace [--top 25] [--iters N]

``--iters N`` divides durations by N (pass the number of timed steps the
capture covered to read per-step costs directly).

Mapping fusion names back to HLO: dump the compiled program via
``jitted.lower(*args).compile().as_text()`` and search for the fusion name —
each carries ``metadata={op_name=... source_file=...}`` pointing at the
Python that emitted it.

Events here are DEVICE timeline spans. By default,
nested spans (e.g. a while loop and the fusions inside it) each carry their
full duration, so the table over-counts hierarchies — read it top-down, or
pass ``--self-time`` to subtract every span's nested children before
ranking (each op then carries only its exclusive time, and the totals sum
to real device time instead of over-counting).
"""
import argparse
import collections
import glob
import gzip
import json
import os
import sys


def load_trace(trace_dir: str) -> dict:
    pats = [os.path.join(trace_dir, "**", "*.trace.json.gz"),
            os.path.join(trace_dir, "**", "*.trace.json")]
    hits = sorted(h for p in pats for h in glob.glob(p, recursive=True))
    if not hits:
        raise SystemExit(f"no *.trace.json[.gz] under {trace_dir} — was it "
                         "captured with jax.profiler.trace / "
                         "profile_trace_dir=?")
    # newest capture run wins (run dirs are timestamps); a multi-process
    # capture writes one trace per host into that run — summarize ONE host
    # and say so rather than silently merging or dropping
    run_dir = os.path.dirname(hits[-1])
    run_hits = [h for h in hits if os.path.dirname(h) == run_dir]
    path = run_hits[-1]
    if len(run_hits) > 1:
        print(f"NOTE: {len(run_hits)} host traces in this capture; "
              f"summarizing {os.path.basename(path)} only", file=sys.stderr)
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def _self_durations(events):
    """``(name, dur_minus_nested_children)`` per event: a per-(pid, tid)
    stack walk over start-sorted complete events, subtracting each span's
    DIRECT children from it (grandchildren subtract from their own parent),
    so totals sum to real device time instead of over-counting nests."""
    out = []
    tracks = collections.defaultdict(list)
    for e in events:
        tracks[(e.get("pid"), e.get("tid"))].append(e)
    for track in tracks.values():
        # ties: the longer span is the parent and must be pushed first
        track.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []  # [end_ts, child_dur_sum, name, dur]
        for e in track:
            while stack and stack[-1][0] <= e["ts"]:
                end, child, name, dur = stack.pop()
                out.append((name, max(dur - child, 0)))
            if stack:
                stack[-1][1] += e["dur"]
            stack.append([e["ts"] + e["dur"], 0, e["name"], e["dur"]])
        while stack:
            end, child, name, dur = stack.pop()
            out.append((name, max(dur - child, 0)))
    return out


def device_op_table(trace: dict, self_time: bool = False):
    """[(name, total_us)] for complete events on device-side process rows.

    ``self_time=True`` ranks by exclusive duration (nested children
    subtracted) instead of inclusive — the fix for the hierarchy
    over-count this module's docstring warns about."""
    events = trace.get("traceEvents", [])
    proc_names = {e["pid"]: e.get("args", {}).get("name", "")
                  for e in events
                  if e.get("ph") == "M" and e.get("name") == "process_name"}
    device_events = []
    for e in events:
        if e.get("ph") == "X" and "dur" in e:
            pname = proc_names.get(e.get("pid"), "")
            if "TPU" in pname or "GPU" in pname:
                device_events.append(e)
    per_op = collections.Counter()
    if self_time:
        for name, dur in _self_durations(device_events):
            per_op[name] += dur
    else:
        for e in device_events:
            per_op[e["name"]] += e["dur"]
    return per_op.most_common()


def main() -> None:
    ap = argparse.ArgumentParser(
        description="per-op device-time summary of a jax.profiler trace")
    ap.add_argument("trace_dir")
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--iters", type=int, default=1,
                    help="timed steps in the capture: durations are "
                         "divided by this")
    ap.add_argument("--self-time", action="store_true",
                    help="rank by exclusive time (nested children "
                         "subtracted) — totals then sum to real device "
                         "time instead of over-counting hierarchies")
    args = ap.parse_args()
    if args.iters < 1:
        ap.error("--iters must be >= 1")

    table = device_op_table(load_trace(args.trace_dir),
                            self_time=args.self_time)
    if not table:
        raise SystemExit("no device-side complete events found (CPU-only "
                         "trace? the device timeline needs a TPU/GPU run)")
    total = sum(us for _, us in table)
    print(f"{'ms/iter':>10}  {'share':>6}  op")
    for name, us in table[:args.top]:
        print(f"{us / args.iters / 1e3:10.2f}  {us / total * 100:5.1f}%  "
              f"{name[:100]}")
    kind = ("self time (exclusive, nests subtracted)" if args.self_time
            else "inclusive time (nested spans over-count; read top-down, "
                 "or use --self-time)")
    print(f"\ntotal device {kind}: {total / args.iters / 1e3:.1f} ms/iter")
    sys.exit(0)


if __name__ == "__main__":
    main()
