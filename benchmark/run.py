#!/usr/bin/env python3
"""One cell, one process, one line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets up the cell ``BENCHMARK.json`` names, warms up every shape its traffic
uses, measures for ``--seconds`` seconds and prints one JSON object as the
last line of its standard output: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and last
``compared`` (each number the reference check compared, with its limit;
the same on the last lines of standard error). With
``--trace 0`` the metrics are the cell's end-to-end metrics, with ``--trace 1``
its per-layer metrics. It exits non-zero and prints no such line when JAX
finds no TPU, fewer chips than the cell asks for or a device the peaks table
does not know, and beside a missing program. Everything else worth keeping
goes on earlier lines and into ``benchmark_out/<cell>/last_run.json``.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time
import traceback
from pathlib import Path
from typing import Any, Dict, List, Optional

_IMPORTED = time.perf_counter()
BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

from vftbench import (device, manifest, program, stats, timeline,  # noqa: E402
                      tracing)
from vftbench.measurement import Measurement  # noqa: E402

EXIT_NO_CHIP = 3
EXIT_NO_PROGRAM = 4
EXIT_BAD_MANIFEST = 2
EXIT_FAILED = 1


def process_started() -> float:
    """When this process started, on the ``perf_counter`` clock: set-up
    counts the interpreter's own start too. From ``/proc``; where that cannot
    be read, the instant this file was imported."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        if 0.0 <= age < 3600.0:
            return time.perf_counter() - age
    except (OSError, ValueError, IndexError):
        pass
    return _IMPORTED


def reference_check(cell: manifest.Cell, result: Dict[str, Any],
                    out_dir: Path) -> Dict[str, Any]:
    """The timed path's features of the fixed check input against a
    reference, judged by ``checks/<config>.py compare()``. The reference is
    ``references/<config>.py features(params, config, check_path)`` where
    the configuration brings that file: the benchmark's own plain copy. It is
    handed the parameter tree the window ran, in the type it was served in,
    and the configuration, never the extractor: it re-derives the unrounded
    weights and holds the tree against them (PERF.md section 8). Without the
    file it is the program's own twin at the configuration's
    ``reference_keys`` (float32) on the same seeded weights, built once the
    timed extractor is dropped. Either runs under matmul precision "highest"
    and after the window: building a float32 extractor changes JAX's global
    matmul precision."""
    import jax
    extractor = result.pop("extractor")
    key = str(extractor.feature_type)
    features = cell.optional_config_function("references", "features")
    with jax.default_matmul_precision("highest"):
        if features is not None:
            ran = f"references/{cell.config_name}.py, handed the timed " \
                  "parameter tree"
            params = extractor.runner.params
            del extractor  # the reference gets no method of the program's
            reference = features(params, cell.config, result["check_video"])
        else:
            ran = f"the program's twin at reference_keys " \
                  f"{json.dumps(cell.config['reference_keys'])}"
            del extractor  # its weights go before the twin's arrive
            args = program.program_args(cell.config,
                                        out_dir / "run" / "reference",
                                        cell.config["reference_keys"])
            reference = program.build_extractor(args).extract(
                result["check_video"])
    compare = cell.config_function("checks", "compare")
    return {"reference": ran,
            **compare(result["check_feats"], reference, key)}


def metric_values(cell: manifest.Cell, entries: List[dict], m: Measurement
                  ) -> Dict[str, Dict[str, Any]]:
    """``{name: {"value", "unit"}}`` of every metric whose reader found
    something to read."""
    out = {}
    for entry in entries:
        value = cell.reader(entry["name"])(m)
        if value is None:
            print(f"vftbench: {entry['name']}: nothing to read, left out")
            continue
        out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def main(argv: Optional[List[str]] = None, root: Path = manifest.ROOT) -> int:
    started = process_started()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    opts = ap.parse_args(argv)
    try:
        cell = manifest.Cell(manifest.load_manifest(root), opts.workload, root)
    except (manifest.ManifestError, KeyError) as e:
        print(f"vftbench: {e}", file=sys.stderr)
        return EXIT_BAD_MANIFEST
    if importlib.util.find_spec("video_features_tpu") is None:
        print("vftbench: the program under test (video_features_tpu) is not "
              f"beside the benchmark in {root}", file=sys.stderr)
        return EXIT_NO_PROGRAM
    program.place_compile_cache(root)
    try:
        chip = device.require_chip(cell.chips, cell.bench / "peaks.json")
    except device.NoChip as e:
        print(f"vftbench: cannot measure {cell.name}: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    program.cache_small_programs()
    out_dir = Path(root) / "benchmark_out" / cell.name
    out_dir.mkdir(parents=True, exist_ok=True)

    from vftbench import resident, serving
    driver = {"backlog": serving.run, "poisson": serving.run,
              "resident": resident.run}.get(cell.traffic["driver"])
    if driver is None:
        print(f"vftbench: traffic {cell.traffic_name!r} names no known "
              f"driver: {cell.traffic['driver']!r}", file=sys.stderr)
        return EXIT_BAD_MANIFEST
    try:
        result = driver(cell, opts.seed, opts.seconds, bool(opts.trace),
                        out_dir, started)
        m: Measurement = result["measurement"]
        m.peaks = chip["peaks"]
        m.costs = cell.config_function("costs", "per_unit")(cell.config)
        if m.trace_path is not None:
            # onto the trace's clock: seconds from its session's start
            zero = m.trace_zero_perf
            uncertainty_s = m.trace_open_perf - zero
            m.trace = tracing.reduce_trace(
                tracing.load_xplane(m.trace_path),
                m.trace_open_perf - zero, m.trace_close_perf - zero,
                [(n, s - zero, d) for n, s, d in m.stage_spans],
                uncertainty_s, chips=cell.chips)
            print(f"vftbench: traced {m.trace['window_s']:.3f} s; the two "
                  f"clocks agree to within {uncertainty_s * 1e3:.1f} ms")
            # once, for every reader that wants it; renames the breakdown
            timeline.analysis(m)
        # read before the float32 twin below adds programs of its own
        m.memory_peak_bytes = device.memory_peak_bytes(chip["devices"])
        print(f"vftbench: memory peak {m.memory_peak_at_open_bytes / 1e9:.3f}"
              f" GB as the window opened, {m.memory_peak_bytes / 1e9:.3f} GB "
              "after it")
        if m.wire_batches():
            print("vftbench: wire batches dispatched in the window (rows "
                  f"after padding: dispatches): {m.wire_batches()}")
        verdict = reference_check(cell, result, out_dir)
    except Exception:
        traceback.print_exc()
        print(f"vftbench: {cell.name} did not run to its end",
              file=sys.stderr)
        return EXIT_FAILED

    # -- the verdict: every part is printed, and all have to hold -------------
    late_p90 = stats.percentile(result["lateness_s"], 90.0)
    parts = {
        "every request answered done with sound artifacts":
            not result["failed"] and result["attempted"] > 0,
        "agrees with the reference on the check input": bool(verdict["ok"]),
        "no program compiled or loaded inside the window":
            result["compiles_in_window"] == 0,
        "the generator kept time (lateness p90 <= 20 ms)":
            late_p90 is None or late_p90 <= 0.020,
    }
    for line in result["failed"][:10]:
        print(f"vftbench: FAILED {line}")
    # which reference ran; its numbers are the line's ``compared``
    print(f"vftbench: reference check against {verdict['reference']}: "
          + ("agrees" if verdict["ok"] else
             f"DISAGREES {verdict.get('why', '')}".rstrip()))
    if late_p90 is not None:
        print(f"vftbench: generator lateness p50 "
              f"{stats.median(result['lateness_s']) * 1e3:.3f} ms, p90 "
              f"{late_p90 * 1e3:.3f} ms over {len(result['lateness_s'])} "
              "requests")
    for what, ok in parts.items():
        print(f"vftbench: {'ok  ' if ok else 'FAIL'} {what}")

    entries = cell.per_layer if opts.trace else cell.end_to_end
    metrics = metric_values(cell, entries, m)
    if opts.trace:  # the traced run's own end-to-end numbers, for the record
        beside = metric_values(cell, [e for e in cell.end_to_end
                                      if e["name"] != "setup_s"], m)
        print(f"vftbench: end-to-end under tracing (its overhead shows "
              f"against the untraced median): {json.dumps(beside)}")
    line: Dict[str, Any] = {
        "correct": all(parts.values()),
        "attempted": int(result["attempted"]),
        "failed": len(result["failed"]),
        "metrics": metrics,
        "device": {"platform": chip["platform"], "kind": chip["kind"],
                   "count": chip["count"],
                   "memory_peak_bytes": m.memory_peak_bytes},
    }
    if m.trace is not None:
        line["device"]["busy_s"] = m.trace["busy_s"]
        line["device"]["window_s"] = m.trace["window_s"]
        line["breakdown"] = {"device_ops": m.trace["device_ops"],
                             "idle_gaps": m.trace["idle_gaps"]}
    # every number the reference check compared, beside its limit: last in
    # the line and on the last lines of standard error, which is what the
    # driver keeps of a run that is not correct
    compared = {name: {"value": verdict.get(name), "limit": limit}
                for name, limit in verdict.get("bands", {}).items()}
    line["compared"] = compared
    details = {"cell": cell.name, "seed": opts.seed, "seconds": opts.seconds,
               "trace": opts.trace, "verdict_parts": parts,
               "reference_check": verdict, "window_s": m.window_s,
               "units_in_window": m.units(),
               "requests_in_window": len(m.responses),
               "latencies_s": m.latencies(),
               "compiles_in_window": result["compiles_in_window"],
               "cpu_count": os.cpu_count(), "line": line}
    if m.trace is not None:
        details["trace"] = {k: v for k, v in m.trace.items() if k != "self_s"}
    (out_dir / "last_run.json").write_text(json.dumps(details, indent=1))
    print(f"vftbench: window {m.window_s:.3f} s, {m.units()} units, "
          f"{len(m.latencies()) or len(m.responses)} requests counted; "
          f"set-up {m.setup_s:.3f} s")
    sys.stdout.flush()
    print(json.dumps(line))
    for name, pair in compared.items():
        print(f"vftbench: compared {name}: {pair['value']} (limit "
              f"{pair['limit']})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
