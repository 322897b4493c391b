"""The few places where the harness touches the program under test.

Everything the benchmark takes from ``video_features_tpu`` goes through this
file: the config loader, the extractor registry, ``ServeLoop`` and the spool
client, the extractor's runner (``dispatch``, ``bucket_batch_size``,
``fixed_batch``), the global ``StageProfiler``, the in-memory
``TraceRecorder`` and the compile counters.
"""
from __future__ import annotations

import os
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np


def place_compile_cache(root: Path) -> str:
    """Where XLA's persistent cache goes: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names, else the fixed in-checkout
    ``.cache/xla`` (the program's own default). Set before JAX is imported,
    so the program's placement rule sees the variable and points nothing
    anywhere else."""
    placed = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                   str(Path(root) / ".cache" / "xla"))
    os.makedirs(placed, exist_ok=True)
    return placed


def cache_small_programs() -> None:
    """Persist every executable, however quickly it compiled: a skipped
    write is a compile in the next run's set-up."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def program_args(config: Dict[str, Any], run_dir: Path,
                 overrides: Optional[Dict[str, Any]] = None):
    """The program's resolved config for a configuration file's ``run_keys``,
    with its spool, outputs and temporaries under ``run_dir``."""
    from video_features_tpu.config import load_config, sanity_check
    keys = dict(config["run_keys"])
    keys.update(overrides or {})
    keys.update(spool_dir=str(run_dir / "spool"),
                output_path=str(run_dir / "out"),
                tmp_path=str(run_dir / "tmp"))
    args = load_config(config["family"], keys)
    sanity_check(args, require_videos=False)
    return args


def build_extractor(args):
    from video_features_tpu.registry import get_extractor_cls
    return get_extractor_cls(args.feature_type)(args)


def artifact_path(args, video_path: str, key: str) -> str:
    """``{output_path}/{stem}_{key}.npy``: the sink's filename contract."""
    return os.path.join(str(args.output_path),
                        f"{Path(video_path).stem}_{key}.npy")


# -- the runner ---------------------------------------------------------------

def watch_dispatch(runner, seen: List[Tuple[float, tuple, Any, int]]
                   ) -> Callable[[], None]:
    """Record ``(at, shape, dtype, rows after padding)`` of every batch that
    enters ``runner.dispatch`` until the returned function is called. The
    span is the benchmark's, placed around the call into the layer; the
    program is not changed."""
    inner = runner.dispatch

    def dispatch(batch):
        rows = int(batch.shape[0])
        seen.append((time.perf_counter(), tuple(batch.shape), batch.dtype,
                     int(runner.bucket_batch_size(rows))))
        return inner(batch)

    runner.dispatch = dispatch

    def unwatch() -> None:
        del runner.dispatch  # the class's method shows again

    return unwatch


def unit_on_the_wire(seen: List[Tuple[float, tuple, Any, int]]
                     ) -> Tuple[tuple, Any]:
    """The one unit shape and dtype of the batches ``watch_dispatch`` saw."""
    units = {(shape[1:], str(dtype)) for _, shape, dtype, _ in seen}
    if len(units) != 1:
        raise RuntimeError(f"the warm-up video dispatched {len(seen)} "
                           f"batches of unit shapes {units}")
    _, shape, dtype, _ = seen[0]
    return tuple(shape[1:]), dtype


def bucket_ladder(runner) -> List[int]:
    """Every batch size the runner can put on the wire for a host batch of
    at most ``fixed_batch`` rows."""
    return sorted({int(runner.bucket_batch_size(n))
                   for n in range(1, int(runner.fixed_batch) + 1)})


def warm_ladder(runner, unit_shape: tuple, dtype) -> List[int]:
    """Run one batch of zeros through every wire shape, so that each is
    compiled (or loaded from the cache) before the window opens."""
    ladder = bucket_ladder(runner)
    for rows in ladder:
        np.asarray(runner.dispatch(np.zeros((rows,) + tuple(unit_shape),
                                            dtype)))
    return ladder


# -- counters and spans -------------------------------------------------------

def compile_events() -> int:
    """Programs built or loaded since the process started, as
    ``jax.monitoring`` counted them for the program's recorder: every
    compile request is a hit or a miss of the persistent cache."""
    from video_features_tpu.telemetry.recorder import (
        compile_cache_baseline, compile_cache_summary)
    compile_cache_baseline()  # installs the listeners once
    total = compile_cache_summary({})
    return int(total.get("hits", 0)) + int(total.get("misses", 0))


def collect_stage_spans(spans: List[Tuple[str, float, float]]
                        ) -> Callable[[], None]:
    """Feed every ``profiler.stage`` call of every thread into ``spans`` as
    ``(stage, start, duration)`` until the returned function is called."""
    from video_features_tpu.utils.profiling import profiler
    profiler.set_trace_hook(
        lambda name, t0, dt: spans.append((name, t0, dt)))
    return lambda: profiler.set_trace_hook(None)


def start_recorder() -> Callable[[], None]:
    """Start the program's span recorder in memory (``TraceRecorder(None)``:
    nothing is written) for a traced run; the returned function closes it,
    after which ``telemetry.trace.last_recording()`` hands its events
    over."""
    from video_features_tpu.telemetry.trace import TraceRecorder
    return TraceRecorder(None).start().close
