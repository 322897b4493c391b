#!/usr/bin/env python
"""Bench trajectory: append bench.py rounds to BENCH_history.jsonl and
flag round-over-round throughput regressions.

``bench.py`` prints one JSON line per round and the driver snapshots it
into ``BENCH_r0N.json`` files — but nothing ever looked at the
*trajectory*, so a regression only surfaces if someone eyeballs two
files. This script maintains the missing time series:

    # append one or more rounds (driver snapshots or raw bench lines)
    python scripts/bench_history.py append BENCH_r0*.json
    python bench.py | tail -1 | python scripts/bench_history.py append -

    # compare the last two rounds of every metric
    python scripts/bench_history.py check
    python scripts/bench_history.py check --band 0.15 --fail-on-regression

Accepted inputs: a driver snapshot (``{"n": N, "parsed": {...}}``), a
raw bench line (``{"metric": ..., "value": ..., "metrics": [...]}``) or
``-`` for stdin. Appends are idempotent per (round, source): re-running
``append`` over the same files does not duplicate history.

``check`` flattens every record into per-metric series and compares the
newest value against the previous round within a noise band (default
20%; the run-to-run spread of these rows was never measured, so treat
flags as "look here", and tighten ``--band`` only on rows you know are
stable). Direction of goodness is
inferred: throughput rows (unit containing ``/sec``, or ratio rows like
the sharing ratio) regress DOWN; overhead rows (``x wall-clock``)
regress UP. With ``--fail-on-regression`` a flag exits 1 for CI/driver
pipelines; otherwise flags are printed and the exit stays 0.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from video_features_tpu.telemetry.jsonl import append_jsonl, read_jsonl  # noqa: E402

SCHEMA_VERSION = "vft.bench_history/1"
HISTORY_FILENAME = "BENCH_history.jsonl"
REPO_ROOT = Path(__file__).resolve().parent.parent

#: tiered retention for bench rounds — telemetry/history.py's downsample
#: algorithm with cadences matched to merge-time benching instead of
#: 30s heartbeats: every round for a month, dailies for half a year,
#: weeklies for two, nothing past that. Without this the file grows one
#: record per CI round forever (the same unbounded-growth bug the
#: heartbeat history already solved — share the fix, don't refix it).
BENCH_TIERS = ((30 * 86400.0, 0.0),
               (180 * 86400.0, 86400.0),
               (730 * 86400.0, 7 * 86400.0))

#: records tolerated before ``append`` auto-compacts
BENCH_COMPACT_AFTER = 256


def default_history_path() -> str:
    return str(REPO_ROOT / HISTORY_FILENAME)


def parse_round(text: str, source: str) -> Optional[dict]:
    """One input document -> one history record, or None if unparseable.

    Driver snapshots carry the bench line under ``parsed`` and the round
    number under ``n``; a raw bench line is used as-is (round inferred
    later as max+1 when absent).
    """
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        # driver snapshots may hold the line inside a text tail; find the
        # last parseable {"metric": ...} line instead of giving up
        for line in reversed(text.splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    doc = json.loads(line)
                    break
                except json.JSONDecodeError:
                    continue
        else:
            return None
    if not isinstance(doc, dict):
        return None
    rnd = doc.get("n")
    parsed = doc.get("parsed") if isinstance(doc.get("parsed"), dict) \
        else None
    if parsed is None and "metric" in doc:
        parsed = doc
    if parsed is None or "metric" not in parsed:
        return None
    return {
        "schema": SCHEMA_VERSION,
        "round": int(rnd) if rnd is not None else None,
        "source": os.path.basename(source),
        "recorded_time": round(time.time(), 3),
        "headline": {k: parsed.get(k) for k in
                     ("metric", "value", "unit", "vs_baseline",
                      "mfu", "effective_tflops")},
        "metrics": [m for m in parsed.get("metrics", [])
                    if isinstance(m, dict) and "metric" in m],
    }


def load_history(path: str) -> List[dict]:
    return [r for r in read_jsonl(path)
            if r.get("schema") == SCHEMA_VERSION]


def append_rounds(path: str, inputs: List[str]) -> int:
    history = load_history(path)
    seen = {(r.get("round"), r.get("source")) for r in history}
    max_round = max((r.get("round") or 0 for r in history), default=0)
    added = 0
    for src in inputs:
        if src == "-":
            text, name = sys.stdin.read(), "<stdin>"
        else:
            try:
                text = open(src, encoding="utf-8").read()
            except OSError as e:
                print(f"WARNING: cannot read {src}: {e}", file=sys.stderr)
                continue
            name = src
        rec = parse_round(text, name)
        if rec is None:
            print(f"WARNING: no bench line found in {name}",
                  file=sys.stderr)
            continue
        if rec["round"] is None:
            max_round += 1
            rec["round"] = max_round
        else:
            max_round = max(max_round, rec["round"])
        key = (rec["round"], rec["source"])
        if key in seen:
            continue  # idempotent re-append
        append_jsonl(path, rec)
        seen.add(key)
        added += 1
    print(f"bench history: {added} round(s) appended to {path} "
          f"({len(seen)} total)")
    if added and len(load_history(path)) > BENCH_COMPACT_AFTER:
        compact_history(path)
    return 0


def compact_history(path: str, now: Optional[float] = None) -> int:
    """Rewrite the history through the heartbeat-history downsampler
    (telemetry/history.py) with bench-cadence tiers. Records carry
    ``recorded_time``, not ``time`` — shimmed in and stripped back out.
    Atomic temp+replace; returns the retained count."""
    from video_features_tpu.telemetry.history import downsample
    history = load_history(path)
    shimmed = [{**r, "time": r.get("recorded_time")} for r in history
               if r.get("recorded_time") is not None]
    kept = downsample(shimmed, now=now, tiers=BENCH_TIERS)
    if len(kept) == len(history):
        return len(history)
    tmp = path + ".compact.tmp"
    try:
        # vft-lint: disable=VFT004 — temp+fsync+os.replace in place (line-oriented rewrite, same discipline as HistoryWriter.compact)
        with open(tmp, "w", encoding="utf-8") as f:
            for s in kept:
                s = {k: v for k, v in s.items() if k != "time"}
                f.write(json.dumps(s, sort_keys=True) + "\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    print(f"bench history: compacted {len(history)} -> {len(kept)} "
          f"round(s) in {path}")
    return len(kept)


# -- regression check -------------------------------------------------------

#: device-efficiency fields bench.py stamps on its rows (ISSUE 12:
#: telemetry/roofline.py) — each becomes its OWN derived series so the
#: regression gate guards efficiency, not just the row's primary value
EFFICIENCY_FIELDS = ("mfu", "effective_tflops")


def _rows(rec: dict) -> List[dict]:
    rows = []
    h = rec.get("headline") or {}
    if h.get("metric") is not None and h.get("value") is not None:
        rows.append(h)
    rows += [m for m in rec.get("metrics", []) if m.get("value") is not None]
    # mfu/effective_tflops ride throughput rows as extra fields; split
    # them into "<row> [mfu]"-style series of their own, with the field
    # name as the unit so lower_is_better classifies them by field (a
    # parent row named "...overhead..." must not flip its mfu series)
    derived = []
    for row in rows:
        for key in EFFICIENCY_FIELDS:
            v = row.get(key)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                derived.append({"metric": f"{row['metric']} [{key}]",
                                "value": v, "unit": key})
    return rows + derived


def lower_is_better(metric: str, unit: str) -> bool:
    """Overhead/latency rows regress UP; device-efficiency series (the
    roofline fields: MFU, effective TFLOPS) regress DOWN like the
    throughputs they ride — checked FIRST so an efficiency series split
    off an overhead-named row keeps its direction; everything else
    bench.py emits is a higher-is-better throughput or sharing ratio."""
    if unit in EFFICIENCY_FIELDS:
        return False
    text = f"{metric} {unit}".lower()
    if "mfu" in text or "tflops" in text:
        return False
    return "overhead" in text or "wall-clock" in text \
        or "seconds per" in text


def series(history: List[dict]) -> Dict[str, List[Tuple[int, float, str]]]:
    """metric name -> [(round, value, unit)] sorted by round. Bench row
    names are prefix-truncated by bench.py's compactor, so an exact-name
    match across rounds is the correct join key."""
    out: Dict[str, List[Tuple[int, float, str]]] = {}
    for rec in sorted(history, key=lambda r: r.get("round") or 0):
        rnd = rec.get("round") or 0
        for row in _rows(rec):
            try:
                v = float(row["value"])
            except (TypeError, ValueError):
                continue
            out.setdefault(str(row["metric"]), []).append(
                (rnd, v, str(row.get("unit") or "")))
    return out


def check_regressions(path: str, band: float
                      ) -> Tuple[List[str], List[str]]:
    """(regressions, report lines) comparing each metric's newest round
    against its previous one."""
    history = load_history(path)
    if len(history) < 2:
        return [], [f"bench history: {len(history)} round(s) in {path} — "
                    "need 2+ to compare"]
    lines: List[str] = [f"bench history: {len(history)} round(s) in {path}"]
    regressions: List[str] = []
    for metric, pts in sorted(series(history).items()):
        if len(pts) < 2:
            lines.append(f"  new   {metric}: {pts[-1][1]:g} {pts[-1][2]} "
                         f"(round {pts[-1][0]}, no prior round)")
            continue
        (prev_r, prev_v, _), (last_r, last_v, unit) = pts[-2], pts[-1]
        if prev_v == 0:
            continue
        ratio = last_v / prev_v
        worse = ratio > 1.0 + band if lower_is_better(metric, unit) \
            else ratio < 1.0 - band
        tag = "REGRESSION" if worse else "ok"
        lines.append(
            f"  {tag:<10} {metric}: {prev_v:g} -> {last_v:g} {unit} "
            f"({ratio:.2f}x, rounds {prev_r}->{last_r})")
        if worse:
            regressions.append(
                f"{metric}: {prev_v:g} -> {last_v:g} {unit} "
                f"({ratio:.2f}x, beyond the {band:.0%} noise band)")
    return regressions, lines


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("command", choices=("append", "check", "compact"))
    ap.add_argument("inputs", nargs="*",
                    help="append: BENCH_r0N.json snapshots, raw bench "
                         "lines, or '-' for stdin")
    ap.add_argument("--history", default=default_history_path(),
                    help=f"history file (default {HISTORY_FILENAME} at "
                         "the repo root)")
    ap.add_argument("--band", type=float, default=0.2,
                    help="noise band as a fraction (default 0.2 = 20%%)")
    ap.add_argument("--fail-on-regression", action="store_true",
                    help="exit 1 when any metric regresses beyond the "
                         "band (CI/driver gating)")
    args = ap.parse_args(argv)
    if args.command == "append":
        if not args.inputs:
            ap.error("append needs at least one input file (or '-')")
        return append_rounds(args.history, args.inputs)
    if args.command == "compact":
        compact_history(args.history)
        return 0
    regressions, lines = check_regressions(args.history, args.band)
    print("\n".join(lines))
    if regressions:
        print(f"bench history: {len(regressions)} regression(s) beyond "
              f"the {args.band:.0%} band:")
        for r in regressions:
            print(f"  - {r}")
        if args.fail_on_regression:
            return 1
    else:
        print("bench history: no regressions beyond the "
              f"{args.band:.0%} band")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
