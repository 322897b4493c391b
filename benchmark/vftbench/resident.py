"""The model step with the host taken away: the extractor's own runner, fed
device-resident batches of exactly the shape it dispatches for a full group.

Synthetic by construction. The batches hold what
``inputs/<config>.py resident_batch`` draws from the seed (without that file:
random bytes), nothing is decoded, transferred or written inside the window,
and the only host work is the runner's own per-dispatch code. Timing blocks
of about a second end in one host read of the last output, which the
device's in-order queue makes a fence for every dispatch before it.
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


def byte_batch(rng: np.random.Generator, shape: tuple, dtype) -> np.ndarray:
    """What a resident group holds where the configuration brings no
    ``inputs/<config>.py``: seeded bytes in the wire's type."""
    return rng.integers(0, 256, shape, dtype=np.uint8).astype(dtype,
                                                             copy=False)


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

    # one real input through the extractor shows what it puts on the wire
    (check_video,) = corpus.build_fixed(
        out_dir.parent, traffic["check_video"],
        [corpus.frames_for(int(cell.config["check_units"]), unit)],
        cell.corpus_kind(traffic["check_video"])).values()
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
    draw = cell.optional_config_function("inputs", "resident_batch") \
        or byte_batch
    batches = [jax.device_put(np.asarray(draw(rng, full, dtype), dtype))
               for _ in range(int(traffic["resident_batches"]))]
    for b in batches:  # compiles or loads the one program, twice over
        np.asarray(runner.dispatch(b))
    start = time.perf_counter()
    for b in batches:
        out = runner.dispatch(b)
    np.asarray(out)
    per_dispatch = (time.perf_counter() - start) / len(batches)
    per_block = max(1, int(round(float(traffic["block_s"]) / per_dispatch)))

    # a traced run records the program's own timeline of the window in
    # memory: its spans around every dispatch (``mesh.pad``, ``mesh.enqueue``)
    close_recorder = program.start_recorder() if trace else (lambda: None)
    m = Measurement()
    m.memory_peak_at_open_bytes = device.memory_peak_bytes(
        device.chips_of(cell.chips))
    compiles_before, cpu_before = program.compile_events(), os.times()
    m.t0 = time.perf_counter()
    m.setup_s = m.t0 - started
    until = m.t0 + seconds
    if trace:
        length = min(float(traffic["trace_s"]), 0.6 * seconds)
        _blocks(runner, batches, per_block, m, m.t0 + 0.3 * (seconds - length))
        # the profiler starts and stops between two blocks, inside the
        # window, and no block runs meanwhile: those seconds are no part of
        # the rate that was dispatched
        paused = time.perf_counter()
        window = TraceWindow(out_dir / "trace", m)
        shutil.rmtree(window.out_dir, ignore_errors=True)
        window.start()
        m.paused_s += time.perf_counter() - paused
        _blocks(runner, batches, per_block, m, time.perf_counter() + length)
        paused = time.perf_counter()
        window.stop()
        m.paused_s += time.perf_counter() - paused
    _blocks(runner, batches, per_block, m, until)
    m.t1 = time.perf_counter()
    cpu_after = os.times()
    m.cpu_s = ((cpu_after.user + cpu_after.system)
               - (cpu_before.user + cpu_before.system))
    close_recorder()
    if trace:
        print(f"vftbench: {window.took()}, between two blocks: "
              f"{m.paused_s:.3f} s of the window's {m.window_s:.3f} s "
              "dispatched nothing")
    return {"measurement": m, "attempted": len(m.dispatches), "failed": [],
            "compiles_in_window": program.compile_events() - compiles_before,
            "lateness_s": [], "check_video": check_video,
            "check_feats": check_feats, "extractor": extractor}
