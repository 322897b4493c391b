"""Roofline observatory: self-measuring MFU accounting per family.

Until round 12 only r2plus1d had MFU accounting, and it lived as a
hand-computed table in docs/performance.md — S3D's and CLIP's throughput
rows had no saturated-vs-sandbagged verdict, and nothing in CI would
notice a change silently halving a family's device efficiency. In the
compiler-first spirit of PAPERS.md (arxiv 2603.09555) the source of
truth here is the compiler's own cost model — ``lowered.cost_analysis()``,
the exact method behind the old hand table — captured automatically:

  - **cost cards** (:meth:`RooflineObserver.observe_dispatch`, hooked in
    ``parallel/mesh.py DataParallelApply.dispatch``/``__call__`` — the
    same pre-construction seam compile_cache.py attaches at, observed at
    the dispatch boundary): for every distinct ``(runner, padded batch
    shape)`` a one-time AOT lowering records XLA-reported FLOPs, bytes
    accessed and the derived arithmetic intensity; every further
    dispatch just bumps a counter (one global read when ``roofline`` is
    off, one dict hit when on);
  - **measured time** rides the existing ``profiler.stage`` call sites
    (utils/profiling.py): the observer chains onto the stage hook and
    accumulates the steady-state ``forward`` (device stall under async
    dispatch; true H2D+forward+D2H on the synchronous path) and ``h2d``
    stage seconds per family — no new timers in the hot loops;
  - **peak registry** (:data:`PEAK_REGISTRY` + :func:`peak_for_device`):
    known device kinds carry their practical peak (v5e: the 127-TFLOPS
    2048^3-bf16-matmul calibration from docs/performance.md) and HBM
    bandwidth; unknown kinds fall back to :func:`measure_peak` — the
    same 2048^3 bf16 matmul plus a fused read-reduce bandwidth probe —
    cached per device kind so the microbench runs once per machine.

Joining the three yields, per family: effective TFLOPS
(``flops_dispatched / forward_seconds``), **MFU** against the practical
peak, and a roofline position that resolves to ONE of four verdicts
(:func:`classify`):

  ====================  ====================================================
  ``compute-bound``     the device window is explained by FLOPs at peak —
                        saturated; faster means a different program
  ``bandwidth-bound``   below the ridge point and the window is explained
                        by bytes at peak HBM bandwidth — fuse or shrink
                        the wire, not the math
  ``launch-overhead-bound``  neither FLOPs nor bytes explain the window:
                        fixed per-dispatch cost dominates — batch wider
                        or fuse launches
  ``host-bound``        (sandbagged) the device sat idle most of the wall
                        clock waiting for the host — decode/transform is
                        the wall, the chip is not the story
  ====================  ====================================================

Artifacts: ``{output_path}/_roofline.json`` under the checked-in
``telemetry/roofline.schema.json`` (per-host in fleet=queue dirs, like
traces), a live ``roofline`` section in heartbeats + ``_run.json``
(telemetry/recorder.py), per-family lines in ``vft-top``, fleet roll-up
+ ``vft_roofline_mfu{family}`` prom gauges in ``vft-fleet``, and the
``vft-roofline`` report (:func:`report_main`) rendering the MFU table
with an optional per-op ``jax.profiler`` merge. bench.py stamps
``mfu``/``effective_tflops`` on its device rows from the same
:func:`program_cost` arithmetic, so ``bench_history.py
--fail-on-regression`` now guards device efficiency, not just
throughput. See docs/observability.md "The roofline pillar".

Caveat worth stating once: under async dispatch ``forward`` is the
host's *stall* time materializing results — a lower bound on device
busy time — so a fully-hidden device reads as a small ``forward`` with
a low ``device_share``, which is exactly the ``host-bound`` verdict;
the MFU number is then a ceiling estimate and the verdict, not the
percentage, is the finding. Device-resident fenced loops (bench.py)
have ``forward == device time`` and their MFU is exact.
"""
from __future__ import annotations

import atexit
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from .jsonl import write_json_atomic
from .spans import current_span

SCHEMA_VERSION = "vft.roofline/1"
ROOFLINE_FILENAME = "_roofline.json"

#: the four roofline positions (docstring table); the schema enum and
#: check_roofline_schema.py pin this exact set
VERDICTS = ("compute-bound", "bandwidth-bound", "launch-overhead-bound",
            "host-bound")

#: classification thresholds (classify()): device busy share below which
#: the device is sandbagged by the host, and the explained-time floor
#: below which fixed launch overhead is the only remaining account
HOST_BOUND_SHARE = 0.35
LAUNCH_FRAC = 0.15

#: emitter field lists — check_roofline_schema.py asserts these equal the
#: checked-in schema's properties, so emitter and contract cannot drift
ROOFLINE_FIELDS = ("schema", "run_id", "host_id", "feature_type", "time",
                   "wall_s", "device", "families")
DEVICE_FIELDS = ("platform", "device_kind", "peak_tflops", "nominal_tflops",
                 "peak_gbps", "source")
FAMILY_FIELDS = ("programs", "flops_total", "bytes_total", "dispatches",
                 "forward_s", "forward_calls", "h2d_s", "wall_s",
                 "device_share", "arithmetic_intensity", "effective_tflops",
                 "effective_tflops_wall", "mfu", "verdict")
CARD_FIELDS = ("shape", "dtype", "batch", "flops", "bytes", "intensity",
               "dispatches")

#: per-device-kind practical peaks. ``peak_tflops`` is the DENOMINATOR of
#: every MFU here: the measured practical ceiling where we have one (v5e:
#: a 2048^3 bf16 matmul measures ~127 TFLOPS on the bench chip, 64% of
#: nominal 197 — docs/performance.md), the public nominal bf16 spec
#: otherwise. ``peak_gbps`` is HBM bandwidth (public specs). Matching is
#: by normalized substring, so "TPU v5 lite" and "TPU v5e" resolve alike.
PEAK_REGISTRY: Dict[str, Dict[str, float]] = {
    "tpu v5 lite": {"peak_tflops": 127.0, "nominal_tflops": 197.0,
                    "peak_gbps": 819.0},
    "tpu v5e": {"peak_tflops": 127.0, "nominal_tflops": 197.0,
                "peak_gbps": 819.0},
    "tpu v5p": {"peak_tflops": 459.0, "nominal_tflops": 459.0,
                "peak_gbps": 2765.0},
    "tpu v4": {"peak_tflops": 275.0, "nominal_tflops": 275.0,
               "peak_gbps": 1228.0},
    "tpu v3": {"peak_tflops": 123.0, "nominal_tflops": 123.0,
               "peak_gbps": 900.0},
    "tpu v6": {"peak_tflops": 918.0, "nominal_tflops": 918.0,
               "peak_gbps": 1640.0},
}


def roofline_filename(host_id: Optional[str] = None) -> str:
    """``_roofline.json``, or the per-host ``_roofline_{host_id}.json``
    when N fleet=queue workers co-own one output dir (the trace-file
    discipline: the last worker to exit must not overwrite its
    siblings' accounting)."""
    if host_id is None:
        return ROOFLINE_FILENAME
    import re
    safe = re.sub(r"[^A-Za-z0-9._-]+", "-", str(host_id))
    return f"_roofline_{safe}.json"


# -- the compiler's own cost model -------------------------------------------

def program_cost(fn, *args) -> Dict[str, float]:
    """XLA's cost analysis for one jitted program at these argument
    shapes: ``{"flops": F, "bytes": B}`` — the same
    ``lowered.cost_analysis()`` numbers the old hand table in
    docs/performance.md was derived from (5,039 GF/batch for the B=64
    r21d program). One AOT lowering per call; callers cache per shape."""
    lowered = fn.lower(*args)
    ca = lowered.cost_analysis() or {}
    return {"flops": float(ca.get("flops", 0.0) or 0.0),
            "bytes": float(ca.get("bytes accessed", 0.0) or 0.0)}


# -- peak resolution ----------------------------------------------------------

def _peak_cache_root() -> str:
    return os.environ.get(
        "VFT_ROOFLINE_CACHE_DIR",
        os.path.join(os.path.expanduser("~"), ".cache",
                     "video_features_tpu", "roofline"))


def _peak_cache_path(device_kind: str, cache_dir: Optional[str]) -> str:
    import re
    safe = re.sub(r"[^A-Za-z0-9._-]+", "-", str(device_kind)) or "unknown"
    return os.path.join(cache_dir or _peak_cache_root(),
                        f"peak_{safe}.json")


def measure_peak(n: int = 2048, band_elems: int = 1 << 25,
                 calls: int = 4, trials: int = 3) -> Dict[str, float]:
    """Microbench the device's practical roofline corners, the
    performance.md calibration method generalized:

      - **peak_tflops**: a ``n``^3 bf16 matmul (default 2048^3 — the
        exact probe that measured 127 TFLOPS on the v5e bench chip),
        reduced to a scalar IN-GRAPH so the fence is a one-float D2H
        read (parallel/mesh.py ``settle``);
      - **peak_gbps**: a fused multiply-add-reduce over ``band_elems``
        f32 elements — one HBM read pass, scalar out — i.e. achievable
        read bandwidth, the roofline's other roof.

    Best of ``trials``, ``calls`` chained dispatches per trial (the
    device's in-order queue makes the final scalar read fence them
    all). Seconds on a cold CPU, microseconds to re-read once cached —
    see :func:`peak_for_device` for the per-device-kind cache."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(0)
    a = jax.device_put(rng.standard_normal((n, n), dtype=np.float32)
                       .astype(jnp.bfloat16))
    b = jax.device_put(rng.standard_normal((n, n), dtype=np.float32)
                       .astype(jnp.bfloat16))
    mm = jax.jit(lambda x, y: jnp.sum((x @ y).astype(jnp.float32)))
    float(mm(a, b))  # compile + warm
    best_tf = 0.0
    for _ in range(trials):
        t0 = time.perf_counter()
        out = None
        for _ in range(calls):
            out = mm(a, b)
        float(out)  # D2H fence
        dt = time.perf_counter() - t0
        best_tf = max(best_tf, calls * 2.0 * n ** 3 / dt / 1e12)

    x = jax.device_put(np.arange(band_elems, dtype=np.float32))
    rd = jax.jit(lambda v: jnp.sum(v * 1.0001 + 0.5))
    float(rd(x))
    best_gb = 0.0
    for _ in range(trials):
        t0 = time.perf_counter()
        out = None
        for _ in range(calls):
            out = rd(x)
        float(out)
        dt = time.perf_counter() - t0
        best_gb = max(best_gb, calls * band_elems * 4.0 / dt / 1e9)
    return {"peak_tflops": round(best_tf, 3), "peak_gbps": round(best_gb, 2),
            "matmul_n": n, "band_bytes": band_elems * 4}


def registry_peak(device_kind: str) -> Optional[Dict[str, float]]:
    """Registry entry for a device kind (normalized substring match), or
    None for unknown hardware (the microbench fallback's cue)."""
    norm = " ".join(str(device_kind).lower().split())
    for key, entry in PEAK_REGISTRY.items():
        if key in norm or norm in key:
            return dict(entry)
    return None


def peak_for_device(device_kind: Optional[str] = None,
                    platform: Optional[str] = None,
                    cache_dir: Optional[str] = None,
                    measure: bool = True,
                    measure_fn=measure_peak) -> Optional[Dict[str, Any]]:
    """The MFU denominator for this process's device, resolved in
    precedence order:

      1. ``VFT_ROOFLINE_PEAK="tflops,gbps"`` env override (tests, CI
         smokes, operators with their own calibration);
      2. :data:`PEAK_REGISTRY` by device kind;
      3. a cached prior :func:`measure_peak` result for this kind;
      4. the microbench itself (cached for next time) — skipped when
         ``measure=False`` (returns None: heartbeat snapshots must
         never block on a matmul).

    Returns ``{platform, device_kind, peak_tflops, nominal_tflops,
    peak_gbps, source}`` (the schema's ``device`` block)."""
    env = os.environ.get("VFT_ROOFLINE_PEAK")
    if env:
        try:
            tf, gb = (float(v) for v in env.split(",")[:2])
        except ValueError:
            raise ValueError(
                f"VFT_ROOFLINE_PEAK={env!r}: expected 'tflops,gbps' "
                "(e.g. '127,819')") from None
    if device_kind is None or platform is None:
        try:
            import jax
            devs = jax.local_devices()
            if device_kind is None:
                device_kind = getattr(devs[0], "device_kind", "?") \
                    if devs else "?"
            if platform is None:
                platform = devs[0].platform if devs else "?"
        except Exception:
            pass  # env-pinned peaks must work without a live backend
    if env:
        return {"platform": platform, "device_kind": device_kind,
                "peak_tflops": tf, "nominal_tflops": tf, "peak_gbps": gb,
                "source": "env"}
    base = {"platform": platform, "device_kind": device_kind}
    reg = registry_peak(device_kind)
    if reg is not None:
        return {**base, **reg, "source": "registry"}
    cache_path = _peak_cache_path(device_kind, cache_dir)
    try:
        with open(cache_path, encoding="utf-8") as f:
            cached = json.load(f)
        if isinstance(cached, dict) and cached.get("peak_tflops"):
            return {**base, "peak_tflops": float(cached["peak_tflops"]),
                    "nominal_tflops": None,
                    "peak_gbps": float(cached.get("peak_gbps") or 0) or None,
                    "source": "microbench (cached)"}
    except (OSError, ValueError):
        pass
    if not measure:
        return None
    m = measure_fn()
    try:
        write_json_atomic(cache_path, {**m, "device_kind": device_kind,
                                       "time": round(time.time(), 3)})
    except OSError:
        pass  # unwritable cache root: measure again next process
    return {**base, "peak_tflops": m["peak_tflops"], "nominal_tflops": None,
            "peak_gbps": m["peak_gbps"], "source": "microbench"}


# -- the verdict --------------------------------------------------------------

def classify(flops: float, bytes_accessed: float, forward_s: float,
             wall_s: float, peak_tflops: Optional[float],
             peak_gbps: Optional[float]) -> Optional[str]:
    """One of the four :data:`VERDICTS` for a family's run, or None when
    the inputs cannot support a verdict (no dispatches, no peak).

    The attribution is the roofline identity read backwards: the minimum
    device time for the dispatched work is
    ``max(flops/peak_flops, bytes/peak_bw)``; whichever term explains
    the *observed* device window is the bound, and a window neither term
    explains (both fractions under :data:`LAUNCH_FRAC`) is fixed
    per-dispatch overhead. Before any of that, a device window that is a
    small share of the wall clock (< :data:`HOST_BOUND_SHARE`) means the
    chip sat idle waiting to be fed — host-bound, the sandbagged case
    ROADMAP item 5 wanted named."""
    if not flops or forward_s is None or forward_s <= 0 or not wall_s:
        return None
    if forward_s / wall_s < HOST_BOUND_SHARE:
        return "host-bound"
    if not peak_tflops:
        return None
    compute_frac = flops / (peak_tflops * 1e12) / forward_s
    bw_frac = (bytes_accessed / (peak_gbps * 1e9) / forward_s
               if peak_gbps else 0.0)
    if max(compute_frac, bw_frac) < LAUNCH_FRAC:
        return "launch-overhead-bound"
    return "compute-bound" if compute_frac >= bw_frac else "bandwidth-bound"


# -- the observer -------------------------------------------------------------

_lock = threading.Lock()
_active: Optional["RooflineObserver"] = None


def active() -> Optional["RooflineObserver"]:
    return _active


def observe_dispatch(runner, padded) -> None:
    """The mesh-layer hook (parallel/mesh.py DataParallelApply): one
    global read when roofline is off; card capture / dispatch count when
    on. Observation must never fail the pipeline."""
    obs = _active
    if obs is not None:
        try:
            obs.observe_dispatch(runner, padded)
        except Exception:
            pass


def snapshot() -> dict:
    """The heartbeat section: the active observer's light per-family
    summary, ``{}`` when roofline is off (zero footprint — the off-path
    heartbeat is byte-identical to pre-roofline builds modulo this
    constant empty key)."""
    obs = _active
    if obs is None:
        return {}
    try:
        return obs.light_summary()
    except Exception:
        return {}


def ensure_for_extractor(ext) -> None:
    """Library-caller hook (extractors/base.py _extract): a process that
    never went through cli.py still gets an observer homed on the
    extractor's output dir when ``roofline=true``, closed (and its
    ``_roofline.json`` written) at interpreter exit. First observer
    wins, like the compile-cache attach."""
    if _active is not None:
        return
    args = getattr(ext, "args", None)
    if args is None or not bool(args.get("roofline", False)):
        return
    obs = RooflineObserver(str(ext.output_path),
                           default_family=str(ext.feature_type))
    if obs.start() is obs:
        atexit.register(obs.close)


class RooflineObserver:
    """Run-scoped MFU accounting: cost cards per dispatched program +
    per-family forward/h2d stage seconds -> effective TFLOPS, MFU and a
    verdict, written to ``_roofline.json`` at :meth:`close`.

    Process-global like the profiler (one device, one accounting);
    :meth:`start` publishes it (first wins) and chains onto the stage
    hook WITHOUT displacing the telemetry recorder's. The peak resolves
    on a daemon thread so a cold microbench never stalls the pipeline
    start (registry/env/cache hits are instant)."""

    def __init__(self, output_path: str, *,
                 default_family: Optional[str] = None,
                 run_id: Optional[str] = None,
                 host_id: Optional[str] = None) -> None:
        self.output_path = str(output_path)
        self.default_family = default_family
        self.run_id = run_id
        self.host_id = host_id
        self.path = os.path.join(self.output_path,
                                 roofline_filename(host_id))
        self._state = threading.Lock()
        #: (id(runner), shape, dtype) -> card dict (flops None = capture
        #: failed; dispatches still counted)
        self._cards: Dict[Tuple, Dict[str, Any]] = {}
        #: family -> {"forward_s", "forward_calls", "h2d_s"}
        self._stages: Dict[str, Dict[str, float]] = {}
        self._peak: Optional[Dict[str, Any]] = None
        self._peak_thread: Optional[threading.Thread] = None
        self._prev_hook = None
        self._hook_fn = None
        self._t0 = time.perf_counter()
        self._closed = False

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "RooflineObserver":
        global _active
        with _lock:
            if _active is not None:
                return _active
            _active = self
        from ..utils.profiling import profiler
        self._prev_hook = prev = profiler._hook

        def hook(name: str, dt: float) -> None:
            if prev is not None:
                prev(name, dt)
            self._observe_stage(name, dt)

        self._hook_fn = hook
        profiler.set_hook(hook)
        self._t0 = time.perf_counter()
        self._peak_thread = threading.Thread(
            target=self._resolve_peak, name="vft-roofline-peak",
            daemon=True)
        self._peak_thread.start()
        return self

    def close(self, write: bool = True) -> Optional[dict]:
        """Finalize: write ``_roofline.json`` atomically, restore the
        stage hook (only if still ours — the recorder's own close may
        have cleared it already), drop the process-global slot. Returns
        the summary; never raises into a caller's finally."""
        global _active
        if self._closed:
            return None
        self._closed = True
        from ..utils.profiling import profiler
        if profiler._hook is self._hook_fn:
            profiler.set_hook(self._prev_hook)
        with _lock:
            if _active is self:
                _active = None
        try:
            doc = self.summary(resolve_peak=True)
            if write:
                write_json_atomic(self.path, doc)
            return doc
        except Exception as e:
            print(f"roofline: close failed ({type(e).__name__}: {e}) — "
                  "accounting for this run is lost, extraction is not")
            return None

    # -- peak ---------------------------------------------------------------
    def _resolve_peak(self) -> None:
        try:
            peak = peak_for_device()
        except Exception:
            peak = None
        with self._state:
            self._peak = peak

    def peak(self, resolve: bool = False) -> Optional[Dict[str, Any]]:
        with self._state:
            peak = self._peak
        if peak is None and resolve:
            t = self._peak_thread
            if t is not None and t.is_alive():
                t.join(timeout=120.0)
            with self._state:
                peak = self._peak
        return peak

    # -- observation --------------------------------------------------------
    def _family(self) -> str:
        span = current_span()
        if span is not None and getattr(span, "feature_type", None):
            return str(span.feature_type)
        return str(self.default_family or "?")

    def observe_dispatch(self, runner, padded) -> None:
        key = (id(runner), tuple(padded.shape), str(padded.dtype))
        with self._state:
            card = self._cards.get(key)
            if card is not None:
                card["dispatches"] += 1
                return
            # placeholder FIRST: a concurrent sibling dispatching the
            # same shape counts instead of lowering twice
            card = {"family": self._family(),
                    "shape": [int(d) for d in padded.shape],
                    "dtype": str(padded.dtype),
                    "batch": int(padded.shape[0]) if padded.ndim else 1,
                    "flops": None, "bytes": None, "intensity": None,
                    "dispatches": 1}
            self._cards[key] = card
        try:
            cost = program_cost(runner._fn, runner.params, padded)
            flops, nbytes = cost["flops"], cost["bytes"]
            with self._state:
                card["flops"] = flops
                card["bytes"] = nbytes
                card["intensity"] = (round(flops / nbytes, 3)
                                     if nbytes else None)
        except Exception:
            pass  # card stays dispatch-counted, flops unknown

    def _observe_stage(self, name: str, dt: float) -> None:
        if name not in ("forward", "h2d"):
            return
        fam = self._family()
        with self._state:
            st = self._stages.setdefault(
                fam, {"forward_s": 0.0, "forward_calls": 0, "h2d_s": 0.0})
            if name == "forward":
                st["forward_s"] += dt
                st["forward_calls"] += 1
            else:
                st["h2d_s"] += dt

    # -- summaries ----------------------------------------------------------
    def _family_doc(self, fam: str, cards: List[dict], st: Dict[str, float],
                    wall_s: float, peak: Optional[dict]) -> dict:
        flops_total = sum(c["flops"] * c["dispatches"] for c in cards
                          if c.get("flops"))
        bytes_total = sum(c["bytes"] * c["dispatches"] for c in cards
                          if c.get("bytes"))
        dispatches = sum(c["dispatches"] for c in cards)
        fwd = float(st.get("forward_s", 0.0))
        eff = (flops_total / 1e12 / fwd if fwd > 0 and flops_total
               else None)
        eff_wall = (flops_total / 1e12 / wall_s
                    if wall_s > 0 and flops_total else None)
        peak_tf = (peak or {}).get("peak_tflops")
        peak_gb = (peak or {}).get("peak_gbps")
        programs = [{k: c.get(k) for k in CARD_FIELDS}
                    for c in sorted(cards, key=lambda c: -(c["flops"] or 0))]
        return {
            "programs": programs,
            "flops_total": flops_total,
            "bytes_total": bytes_total,
            "dispatches": dispatches,
            "forward_s": round(fwd, 6),
            "forward_calls": int(st.get("forward_calls", 0)),
            "h2d_s": round(float(st.get("h2d_s", 0.0)), 6),
            "wall_s": round(wall_s, 3),
            "device_share": (round(fwd / wall_s, 4) if wall_s > 0
                             else None),
            "arithmetic_intensity": (round(flops_total / bytes_total, 3)
                                     if bytes_total else None),
            "effective_tflops": (round(eff, 4) if eff is not None
                                 else None),
            "effective_tflops_wall": (round(eff_wall, 4)
                                      if eff_wall is not None else None),
            "mfu": (round(eff / peak_tf, 4)
                    if eff is not None and peak_tf else None),
            "verdict": classify(flops_total, bytes_total, fwd, wall_s,
                                peak_tf, peak_gb),
        }

    def summary(self, resolve_peak: bool = False) -> dict:
        """The full ``_roofline.json`` document (schema-shaped)."""
        wall = time.perf_counter() - self._t0
        peak = self.peak(resolve=resolve_peak)
        with self._state:
            cards = [dict(c) for c in self._cards.values()]
            stages = {f: dict(s) for f, s in self._stages.items()}
        by_family: Dict[str, List[dict]] = {}
        for c in cards:
            by_family.setdefault(c.get("family") or "?", []).append(c)
        families = {}
        for fam in sorted(set(by_family) | set(stages)):
            families[fam] = self._family_doc(
                fam, by_family.get(fam, []), stages.get(fam, {}),
                wall, peak)
        device = {k: (peak or {}).get(k) for k in DEVICE_FIELDS}
        if peak is None:
            # kind is knowable even before the resolver thread lands
            try:
                import jax
                devs = jax.local_devices()
                device["platform"] = devs[0].platform if devs else None
                device["device_kind"] = (getattr(devs[0], "device_kind",
                                                 None) if devs else None)
            except Exception:
                pass
            device["source"] = "unresolved"
        return {
            "schema": SCHEMA_VERSION,
            "run_id": self.run_id,
            "host_id": self.host_id,
            "feature_type": self.default_family,
            "time": round(time.time(), 3),
            "wall_s": round(wall, 3),
            "device": device,
            "families": families,
        }

    def light_summary(self) -> dict:
        """The heartbeat-sized view: per-family MFU/verdict without the
        program cards, and WITHOUT forcing the peak (a tick must never
        wait on a microbench — mfu/verdict stay null until the resolver
        thread lands)."""
        doc = self.summary(resolve_peak=False)
        fams = {}
        for fam, f in doc["families"].items():
            fams[fam] = {k: f[k] for k in
                         ("dispatches", "effective_tflops", "mfu",
                          "device_share", "verdict")}
            fams[fam]["gflops_total"] = round(f["flops_total"] / 1e9, 1)
        return {"device": doc["device"], "families": fams}


# -- schema -------------------------------------------------------------------

ROOFLINE_SCHEMA_PATH = os.path.join(os.path.dirname(__file__),
                                    "roofline.schema.json")


def load_roofline_schema() -> dict:
    with open(ROOFLINE_SCHEMA_PATH, encoding="utf-8") as f:
        return json.load(f)


def validate_roofline(doc: dict) -> List[str]:
    from . import schema as tschema
    return tschema.validate(doc, load_roofline_schema())


# -- vft-roofline (the report) ------------------------------------------------

def _load_json(path: str) -> Optional[dict]:
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        return doc if isinstance(doc, dict) else None
    except (OSError, ValueError):
        return None


def find_roofline_files(root: str) -> List[str]:
    """Every ``_roofline*.json`` under ``root`` (per-host fleet files
    included), or the file itself when ``root`` is one."""
    from pathlib import Path
    p = Path(root)
    if p.is_file():
        return [str(p)]
    return [str(q) for q in sorted(p.rglob("_roofline*.json"))]


def aggregate_rooflines(root: str) -> Optional[dict]:
    """Merge every roofline artifact under ``root`` into one per-family
    view (fleet roll-up: flops and forward seconds SUM across hosts,
    effective TFLOPS/MFU recomputed from the sums, the verdict re-derived
    over the merged totals). Returns None when no artifacts exist."""
    docs = [d for d in (_load_json(p) for p in find_roofline_files(root))
            if d is not None and d.get("schema") == SCHEMA_VERSION]
    if not docs:
        return None
    device = docs[0].get("device") or {}
    fams: Dict[str, Dict[str, float]] = {}
    for doc in docs:
        for fam, f in (doc.get("families") or {}).items():
            agg = fams.setdefault(fam, {
                "flops_total": 0.0, "bytes_total": 0.0, "dispatches": 0,
                "forward_s": 0.0, "h2d_s": 0.0, "wall_s": 0.0, "hosts": 0})
            for k in ("flops_total", "bytes_total", "forward_s", "h2d_s",
                      "wall_s"):
                agg[k] += float(f.get(k) or 0.0)
            agg["dispatches"] += int(f.get("dispatches") or 0)
            agg["hosts"] += 1
    peak_tf = device.get("peak_tflops")
    peak_gb = device.get("peak_gbps")
    out = {}
    for fam, a in fams.items():
        eff = (a["flops_total"] / 1e12 / a["forward_s"]
               if a["forward_s"] > 0 and a["flops_total"] else None)
        out[fam] = {
            **{k: round(v, 6) if isinstance(v, float) else v
               for k, v in a.items()},
            "arithmetic_intensity": (
                round(a["flops_total"] / a["bytes_total"], 3)
                if a["bytes_total"] else None),
            "effective_tflops": round(eff, 4) if eff is not None else None,
            "mfu": (round(eff / peak_tf, 4)
                    if eff is not None and peak_tf else None),
            "device_share": (round(a["forward_s"] / a["wall_s"], 4)
                             if a["wall_s"] else None),
            "verdict": classify(a["flops_total"], a["bytes_total"],
                                a["forward_s"], a["wall_s"], peak_tf,
                                peak_gb),
        }
    return {"device": device, "families": out, "n_artifacts": len(docs)}


def render_verdict(verdict: Optional[str]) -> str:
    if verdict == "host-bound":
        return "host-bound (sandbagged)"
    return verdict or "?"


def render_table(agg: dict) -> List[str]:
    dev = agg.get("device") or {}
    lines = [
        "== roofline (per-family MFU) ==",
        f"  device: {dev.get('device_kind')} ({dev.get('platform')})  "
        f"peak={dev.get('peak_tflops')} TFLOPS"
        + (f" / {dev.get('peak_gbps')} GB/s" if dev.get("peak_gbps")
           else "")
        + f"  [{dev.get('source')}]",
        f"  {'family':<12} {'GFLOP':>10} {'AI':>7} {'disp':>6} "
        f"{'fwd s':>8} {'eff TFLOPS':>11} {'MFU':>7} {'dev%':>6}  verdict",
    ]
    for fam, f in sorted((agg.get("families") or {}).items()):
        mfu = f.get("mfu")
        share = f.get("device_share")
        lines.append(
            f"  {fam:<12} {f.get('flops_total', 0) / 1e9:>10.1f} "
            f"{f.get('arithmetic_intensity') or 0:>7.1f} "
            f"{f.get('dispatches', 0):>6} "
            f"{f.get('forward_s', 0):>8.2f} "
            f"{f.get('effective_tflops') if f.get('effective_tflops') is not None else float('nan'):>11.4f} "
            f"{(100 * mfu if mfu is not None else float('nan')):>6.2f}% "
            f"{(100 * share if share is not None else float('nan')):>5.1f}%"
            f"  {render_verdict(f.get('verdict'))}")
    return lines


def _profiler_op_table(profile_dir: str, top: int = 10) -> List[str]:
    """Optional per-op breakdown from a ``jax.profiler`` capture dir
    (``profile_trace_dir=``): total device time by op name, the
    where-inside-the-program complement to the per-program cards. A
    self-contained loader (newest ``*.trace.json[.gz]`` under the dir)
    so the vft-roofline console script works off an installed package,
    not just a checkout."""
    import glob
    import gzip
    cands = sorted(
        glob.glob(os.path.join(profile_dir, "**", "*.trace.json.gz"),
                  recursive=True)
        + glob.glob(os.path.join(profile_dir, "**", "*.trace.json"),
                    recursive=True),
        key=os.path.getmtime)
    if not cands:
        return [f"  (no *.trace.json[.gz] under {profile_dir})"]
    path = cands[-1]
    opener = gzip.open if path.endswith(".gz") else open
    try:
        with opener(path, "rt", encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return [f"  (unreadable profiler trace {path}: "
                f"{type(e).__name__}: {e})"]
    totals: Dict[str, float] = {}
    for ev in doc.get("traceEvents", []):
        if not isinstance(ev, dict) or ev.get("ph") != "X":
            continue
        dur = ev.get("dur")
        if isinstance(dur, (int, float)):
            name = str(ev.get("name", "?"))
            totals[name] = totals.get(name, 0.0) + float(dur)
    if not totals:
        return [f"  (no complete events in {path})"]
    acc = sum(totals.values())
    lines = [f"== per-op breakdown ({os.path.basename(path)}) ==",
             f"  {'ms':>10} {'share':>7}  op"]
    for name, us in sorted(totals.items(), key=lambda kv: -kv[1])[:top]:
        lines.append(f"  {us / 1e3:>10.1f} {100 * us / acc:>6.1f}%  "
                     f"{name[:90]}")
    return lines


def report_main(argv: Optional[List[str]] = None) -> int:
    """``vft-roofline <output_dir> [--profile DIR] [--top N] [--json]``:
    render the per-family MFU table + verdicts from a run's (or fleet's)
    ``_roofline*.json`` artifacts, optionally merged with a
    ``jax.profiler`` capture for the per-op view."""
    import argparse
    import sys
    ap = argparse.ArgumentParser(
        description="per-family MFU table + roofline verdicts from "
                    "_roofline.json artifacts (roofline=true runs)")
    ap.add_argument("root", nargs="?", default=None,
                    help="a roofline=true run's output dir (or a fleet "
                         "root, or a _roofline.json file)")
    ap.add_argument("--calibrate", action="store_true",
                    help="run measure_peak() NOW on an idle machine and "
                         "overwrite this device kind's cached peak — the "
                         "in-run fallback measures on a busy device and "
                         "can under-read on few-core hosts")
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="also render a per-op device-time table from a "
                         "jax.profiler capture (profile_trace_dir=)")
    ap.add_argument("--top", type=int, default=10,
                    help="ops to list under --profile (default 10)")
    ap.add_argument("--json", action="store_true",
                    help="print the aggregated document as JSON instead "
                         "of the table")
    args = ap.parse_args(argv)
    if args.calibrate:
        import jax
        devs = jax.local_devices()
        kind = getattr(devs[0], "device_kind", "?") if devs else "?"
        m = measure_peak()
        path = _peak_cache_path(kind, None)
        write_json_atomic(path, {**m, "device_kind": kind,
                                 "time": round(time.time(), 3)})
        print(f"vft-roofline: calibrated {kind}: "
              f"{m['peak_tflops']} TFLOPS / {m['peak_gbps']} GB/s "
              f"-> {path}")
        if args.root is None:
            return 0
    if args.root is None:
        ap.error("an output dir is required unless --calibrate ran alone")
    agg = aggregate_rooflines(args.root)
    if agg is None:
        print(f"vft-roofline: no {ROOFLINE_FILENAME} under {args.root} — "
              "was the run launched with roofline=true?", file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps(agg, indent=1, sort_keys=True))
    else:
        print("\n".join(render_table(agg)))
    if args.profile:
        print("\n".join(_profiler_op_table(args.profile, args.top)))
    return 0


if __name__ == "__main__":
    raise SystemExit(report_main())
