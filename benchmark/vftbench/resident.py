"""The model step with the host taken away: the extractor's own runner, fed
device-resident batches of exactly the shape it dispatches for a full group.

Synthetic by construction. The batches hold seeded random bytes, nothing is
decoded, transferred or written inside the window, and the only host work is
the runner's own per-dispatch code. Timing blocks of about a second end in
one host read of the last output, which the device's in-order queue makes a
fence for every dispatch before it.
"""
from __future__ import annotations

import os
import shutil
import time
from pathlib import Path
from typing import Any, Dict, List

import numpy as np

from . import corpus, device, program
from .manifest import Cell
from .measurement import Measurement
from .tracing import TraceWindow


def _blocks(runner, batches, per_block: int, m: Measurement, until: float
            ) -> None:
    """Timing blocks until ``until``; every block adds its rate, its
    completion and its dispatches to ``m``."""
    rows = int(batches[0].shape[0])
    while time.perf_counter() < until:
        start = time.perf_counter()
        for i in range(per_block):
            m.dispatches.append((time.perf_counter(), rows, rows))
            out = runner.dispatch(batches[i % len(batches)])
        np.asarray(out)  # the fence: nothing is done until this is read
        end = time.perf_counter()
        m.block_rates.append(per_block * rows / (end - start))
        m.completions.append((end, per_block * rows))


def run(cell: Cell, seed: int, seconds: float, trace: bool, out_dir: Path,
        started: float) -> Dict[str, Any]:
    import jax
    traffic, unit = cell.traffic, cell.config["unit"]
    run_dir = out_dir / "run"
    shutil.rmtree(run_dir, ignore_errors=True)
    run_dir.mkdir(parents=True)
    args = program.program_args(cell.config, run_dir)
    extractor = program.build_extractor(args)
    runner = extractor.runner

    # one real video through the extractor shows what it puts on the wire
    (check_video,) = corpus.build_fixed(
        out_dir.parent, traffic["check_video"],
        [corpus.frames_for(int(cell.config["check_units"]), unit)]).values()
    seen: List[tuple] = []
    unwatch = program.watch_dispatch(runner, seen)
    try:
        check_feats = extractor.extract(check_video)
    finally:
        unwatch()
    unit_shape, dtype = program.unit_on_the_wire(seen)
    full = (int(runner.fixed_batch),) + unit_shape
    print(f"vftbench: a full group is {full} {dtype}; cpu_count "
          f"{os.cpu_count()}")

    rng = corpus.stream(seed, cell.traffic_name, "batches")
    batches = [jax.device_put(rng.integers(0, 256, full, dtype=np.uint8)
                              .astype(dtype, copy=False))
               for _ in range(int(traffic["resident_batches"]))]
    for b in batches:  # compiles or loads the one program, twice over
        np.asarray(runner.dispatch(b))
    start = time.perf_counter()
    for b in batches:
        out = runner.dispatch(b)
    np.asarray(out)
    per_dispatch = (time.perf_counter() - start) / len(batches)
    per_block = max(1, int(round(float(traffic["block_s"]) / per_dispatch)))

    m = Measurement()
    m.memory_peak_at_open_bytes = device.memory_peak_bytes(
        device.chips_of(cell.chips))
    compiles_before, cpu_before = program.compile_events(), os.times()
    m.t0 = time.perf_counter()
    m.setup_s = m.t0 - started
    until = m.t0 + seconds
    trace_path = window = None
    if trace:
        length = min(float(traffic["trace_s"]), 0.6 * seconds)
        _blocks(runner, batches, per_block, m, m.t0 + 0.3 * (seconds - length))
        window = TraceWindow(out_dir / "trace")
        shutil.rmtree(window.out_dir, ignore_errors=True)
        window.start()
        _blocks(runner, batches, per_block, m, time.perf_counter() + length)
        trace_path = window.stop()
    _blocks(runner, batches, per_block, m, until)
    m.t1 = time.perf_counter()
    cpu_after = os.times()
    m.cpu_s = ((cpu_after.user + cpu_after.system)
               - (cpu_before.user + cpu_before.system))
    return {"measurement": m, "attempted": len(m.dispatches), "failed": [],
            "compiles_in_window": program.compile_events() - compiles_before,
            "lateness_s": [], "check_video": check_video,
            "check_feats": check_feats, "trace_path": trace_path,
            "trace_window": window}
