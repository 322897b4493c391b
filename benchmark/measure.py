#!/usr/bin/env python3
"""Run a cell the way the driver does and say how far its runs disagree.

    python3 benchmark/measure.py --workload <cell> [--sets 2] [--seeds 1,2,3,4,5,6]
                                 [--trace-seed 7] [--seconds <s>] [--out <dir>]

Each run is ``benchmark/run.py`` in a process of its own, one after the
other (this parent never imports JAX, so it never holds the chip). For every
end-to-end metric it prints each set's median and spread (the distance
between the quartiles over the median), the wider of the spreads and five
times that, which is what a bound is set from. ``--trace-seed`` adds one
``--trace 1`` run. Every run's output is kept under ``--out``.
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from vftbench import manifest, stats  # noqa: E402


def one_run(cell: str, seed: int, seconds: float, trace: int, log: Path,
            timeout_s: float) -> Optional[Dict[str, Any]]:
    """The run's last line as a dict, or ``None`` if it printed none."""
    argv = [sys.executable, str(BENCH / "run.py"), "--workload", cell,
            "--seed", str(seed), "--seconds", str(seconds),
            "--trace", str(trace)]
    start = time.perf_counter()
    with open(log, "w") as out:
        try:
            rc = subprocess.run(argv, stdout=out, stderr=subprocess.STDOUT,
                                cwd=manifest.ROOT, timeout=timeout_s
                                ).returncode
        except subprocess.TimeoutExpired:
            rc = 124  # run() has killed the child and waited for it
    wall = time.perf_counter() - start
    last = log.read_text(errors="replace").strip().splitlines()[-1:]
    try:
        line = json.loads(last[0]) if rc == 0 else None
    except (ValueError, IndexError):
        line = None
    print(f"measure: {cell} seed {seed} trace {trace}: rc {rc}, "
          f"{wall:.1f} s wall" + ("" if line else f"  (see {log})"),
          flush=True)
    if line is not None:
        line["wall_s"] = wall
    return line


def summarise(sets: List[List[Dict[str, Any]]]) -> Dict[str, Any]:
    names = sorted({n for runs in sets for r in runs for n in r["metrics"]})
    out: Dict[str, Any] = {}
    for name in names:
        per_set = []
        for runs in sets:
            values = [r["metrics"][name]["value"] for r in runs
                      if name in r["metrics"]]
            if name == "setup_s":
                values = values[1:] or values  # the first run compiles
            per_set.append({"values": values, "median": stats.median(values),
                            "spread": stats.spread(values)})
        spreads = [s["spread"] for s in per_set if s["spread"] is not None]
        widest = max(spreads) if spreads else None
        out[name] = {"sets": per_set, "widest_spread": widest,
                     "five_times": None if widest is None else 5 * widest}
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seeds", default="1,2,3,4,5,6")
    ap.add_argument("--trace-seed", type=int)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--out", default="chiprun_out/measure")
    ap.add_argument("--timeout", type=float, default=1200.0)
    opts = ap.parse_args(argv)
    seconds = opts.seconds or float(manifest.load_manifest()["run_seconds"])
    seeds = [int(s) for s in opts.seeds.split(",") if s]
    out = manifest.ROOT / opts.out / opts.workload
    out.mkdir(parents=True, exist_ok=True)
    sets: List[List[Dict[str, Any]]] = []
    all_ok = True
    for si in range(opts.sets):
        runs = []
        for seed in seeds:
            line = one_run(opts.workload, seed, seconds, 0,
                           out / f"set{si}_seed{seed}.log", opts.timeout)
            all_ok &= bool(line and line["correct"])
            if line:
                runs.append({"seed": seed, **line})
        sets.append(runs)
    traced = None
    if opts.trace_seed is not None:
        traced = one_run(opts.workload, opts.trace_seed, seconds, 1,
                         out / f"trace_seed{opts.trace_seed}.log",
                         opts.timeout)
        all_ok &= bool(traced and traced["correct"])
    summary = {"workload": opts.workload, "seconds": seconds, "seeds": seeds,
               "end_to_end": summarise(sets), "sets": sets, "traced": traced}
    (out / "summary.json").write_text(json.dumps(summary, indent=1))
    for name, s in summary["end_to_end"].items():
        meds = ", ".join(f"{x['median']:.6g}" if x["median"] is not None
                         else "-" for x in s["sets"])
        sprs = ", ".join(f"{100 * x['spread']:.2f}%" if x["spread"] is not None
                         else "-" for x in s["sets"])
        five = "-" if s["five_times"] is None else f"{100 * s['five_times']:.2f}%"
        print(f"measure: {opts.workload} {name}: medians {meds}; spreads "
              f"{sprs}; five times the widest {five}")
    if traced:
        print(f"measure: {opts.workload} traced: "
              f"{json.dumps(traced['metrics'])}")
        print(f"measure: {opts.workload} device: "
              f"{json.dumps(traced['device'])}")
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main())
