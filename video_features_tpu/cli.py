"""CLI driver: ``python main.py feature_type=X key=val ...``.

Same surface as reference main.py:7-51: per-feature YAML defaults merged under
CLI dotlist overrides, validated, then a progress-bar loop over the (shuffled)
video list with per-video error isolation. Multi-host runs additionally filter
the list to this host's deterministic shard (parallel/mesh.py).
"""
from __future__ import annotations

import sys
import time
from typing import List, Optional

from tqdm import tqdm

from .config import load_config, parse_dotlist, sanity_check
from .registry import get_extractor_cls
from .utils.lists import form_list_from_user_input
from .utils.sinks import safe_extract


def _enable_compilation_cache(args) -> None:
    """Persistent XLA compilation cache, on by default.

    The serial-reference analog of this cost doesn't exist (torch eager has
    no compile step), but here every (family, resolution, batch) executable
    costs tens of seconds of XLA compile on first use — paying it once per
    *machine* instead of once per *run* matters for the CLI's
    one-process-per-invocation lifecycle. ``compilation_cache_dir=null``
    disables. Placement (the rule compile_cache.env_placement states):
    where ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already read it and
    no key here points the cache anywhere else; otherwise 'auto' is the
    fixed in-checkout directory compile_cache.default_root() names."""
    from . import compile_cache
    cache_dir = args.get("compilation_cache_dir", "auto")
    # CLI values go through yaml.safe_load: `false`/`off`/`no` arrive as
    # bool False, `true` as bool True
    if cache_dir in (None, "null", "false", "") or cache_dir is False:
        return
    if args.get("device") == "cpu" and cache_dir in ("auto", True):
        # XLA:CPU executables bake in the compiling host's CPU features; on a
        # heterogeneous fleet a cache hit from a different machine risks
        # SIGILL (XLA warns loudly and may crash). TPU executables have no
        # such hazard and are where compiles are expensive — so 'auto' only
        # persists for TPU runs; an explicit dir still opts CPU runs in.
        return
    import jax
    if not compile_cache.env_placement():
        if cache_dir == "auto" or cache_dir is True:
            cache_dir = compile_cache.default_root()
        jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    # small executables are worth caching too: the CLI compiles few, reuses
    # them across runs, and the default 1s min-compile-time would skip them
    # (and a skipped write is a recompile no hit/miss counter shows)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def _maybe_init_distributed(args) -> None:
    if bool(args.get("distributed", False)):
        # multi-host pod slice: one process per host, launched by the TPU VM
        # runtime (GKE/gcloud); coordinator/process env comes from the
        # platform, so the no-arg initialize() is correct. Must run BEFORE
        # sanity_check: resolve_device calls jax.devices(), which initializes
        # the backend and would lock process_count() at 1. After this,
        # jax.process_index()/process_count() drive local_shard_of_list.
        import jax
        if str(args.get("device", "")) == "cpu":
            # device=cpu must not claim a chip on a TPU host, and a CPU
            # cluster needs the gloo cross-process collectives client for
            # process_count()/process_index() to reflect the job
            jax.config.update("jax_platforms", "cpu")
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        # tolerate in-process re-runs AND launcher-preinitialized workers
        if not jax.distributed.is_initialized():
            jax.distributed.initialize()


def main(argv: Optional[List[str]] = None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "serve":
        # warm serving mode: `python main.py serve feature_type=...
        # spool_dir=...` routes to the long-lived spool drainer
        # (serve.py; also installed as the `vft-serve` console script)
        from .serve import serve_main
        return serve_main(argv[1:])
    if argv and argv[0] == "gateway":
        # network front door: `python main.py gateway spool_dir=...`
        # routes to the HTTP ingress (gateway.py; also installed as the
        # `vft-gateway` console script)
        from .gateway import gateway_main
        return gateway_main(argv[1:])
    if argv and argv[0] == "loadgen":
        # traffic drills: `python main.py loadgen scenarios/steady.yml
        # --spool ... --base-url ...` replays a seeded scenario against
        # the gateway and publishes the _scenario.json verdict
        # (loadgen.py; also installed as the `vft-loadgen` console
        # script). Exits with the drill verdict.
        from .loadgen import loadgen_main
        raise SystemExit(loadgen_main(argv[1:]))
    if argv and argv[0] == "lint":
        # contract-aware static analysis: `python main.py lint [--json
        # --baseline ...]` proves the repo's cross-file invariants in
        # seconds (lint/; also installed as the `vft-lint` console
        # script). Exits with the lint verdict.
        from .lint.engine import main as lint_main
        raise SystemExit(lint_main(argv[1:]))
    if argv and argv[0] == "parity":
        # numerics observatory: `python main.py parity <run_dir>` renders
        # a run's _parity.jsonl; `python main.py parity certify --config
        # raft.yml --flip dtype=bf16` A/B-certifies a precision flip
        # with per-seam error attribution (telemetry/parity.py; also
        # installed as the `vft-parity` console script, docs/numerics.md)
        from .telemetry.parity import main as parity_main
        raise SystemExit(parity_main(argv[1:]))
    if argv and argv[0] == "warmup":
        # ahead-of-time compile warmup: `python main.py warmup resnet ...`
        # routes to the store populator (compile_cache.py; also installed
        # as the `vft-warmup` console script)
        from .compile_cache import warmup_main
        return warmup_main(argv[1:])
    cli_args = parse_dotlist(argv)
    if "feature_type" not in cli_args:
        raise SystemExit("Usage: main.py feature_type=<family>[,<family>...]"
                         " [key=value ...] | main.py serve feature_type=... "
                         "spool_dir=<dir> (docs/serving.md)")
    from .registry import parse_feature_types
    families = parse_feature_types(cli_args.feature_type)
    multi_mode = len(families) > 1
    if multi_mode:
        # multi-family run: per-family configs (top-level keys shared,
        # `family.key=` overrides private), ONE shared decode pass per
        # video (extractors/multi.py + parallel/fanout.py)
        from .config import load_multi_config, sanity_check_multi
        per_family = load_multi_config(families, cli_args)
        args = per_family[families[0]]
        # the user-level output root, captured BEFORE sanity_check
        # namespaces each family's own path under it: run-scoped
        # artifacts (telemetry) live here, per-family sinks/journals in
        # their subdirs
        out_root = str(args.output_path)
        _maybe_init_distributed(args)
        sanity_check_multi(per_family)
    else:
        per_family = None
        args = load_config(cli_args.feature_type, cli_args)
        _maybe_init_distributed(args)
        sanity_check(args)
        out_root = str(args.output_path)
    mon_baseline = None
    if bool(args.get("telemetry", False)):
        # before anything compiles: the manifest's compile-cache counters
        # cover extractor construction too
        from .telemetry.recorder import compile_cache_baseline
        mon_baseline = compile_cache_baseline()
    _enable_compilation_cache(args)
    verbose = (not multi_mode) and \
        args.get("on_extraction", "print") == "print"
    if verbose:
        print(args.to_yaml())

    # Deterministic fault injection (inject=, utils/inject.py): seeded,
    # replayable faults at named durability sites — chaos testing only.
    # VFT_INJECT overrides the config key (and armed subprocess workers
    # at import). Off (the default): every site is one global read.
    from .utils import inject
    inject_plan = inject.arm_for_run(args.get("inject"))
    if inject_plan is not None:
        print(f"inject: armed plan {inject_plan.spec!r} "
              f"(seed={inject_plan.seed}; docs/chaos.md — replay by "
              "re-running with this exact inject= string)")

    # Fleet-shared compile cache (compile_cache.py): attach this process
    # to its (family, resolved config, environment) entry BEFORE the
    # extractors are even constructed — the init-time compiles (flax
    # model.init of the scan-heavy families costs seconds) are part of
    # the warm set. Verify-before-trust on the way in, sealed in the
    # finally below. Supersedes the per-machine compilation_cache_dir
    # wiring above whenever it resolves enabled. A warm attach means a
    # joining host compiles nothing it has seen before.
    from . import compile_cache
    cc_entry = (compile_cache.attach_for_multi_args(per_family) if multi_mode
                else compile_cache.attach_for_args(args.feature_type, args))
    if cc_entry is not None:
        print(f"compile cache: entry {cc_entry.key[:12]} "
              f"({'warm' if cc_entry.warm_at_attach else 'cold'}, "
              f"{cc_entry.verified} verified"
              + (f", {cc_entry.dropped} dropped" if cc_entry.dropped else "")
              + f") at {cc_entry.dir}")

    if multi_mode:
        from .extractors.multi import MultiExtractor
        extractor = None
        multi = MultiExtractor(per_family)
    else:
        multi = None
        extractor = get_extractor_cls(args.feature_type)(args)
    from .telemetry import startup
    startup.mark("ready")
    run_label = ",".join(families)

    video_paths = form_list_from_user_input(
        args.get("video_paths"), args.get("file_with_video_paths"),
        to_shuffle=True)
    # multi-host partitioning, fleet= config key (sanity_check-validated):
    #   static (default) — keep only this host's deterministic hash shard
    #     of the work list, byte-identical to the pre-queue behavior
    #     (jax.process_count() is 1 when jax.distributed is not up);
    #   queue — every host sees the FULL list and seeds the shared
    #     work-stealing queue instead (parallel/queue.py, constructed
    #     below once the telemetry recorder exists to renew leases)
    fleet_mode = str(args.get("fleet", "static") or "static")
    if fleet_mode != "queue":
        from .parallel.mesh import local_shard_of_list
        video_paths = local_shard_of_list(video_paths)

    # profile=true: per-stage decode/forward/write breakdown at the end;
    # profile_trace_dir=/path: additionally capture a jax.profiler trace
    from .utils.profiling import TraceCapture, profiler
    profiler.enabled = bool(args.get("profile", False))
    profiler.reset()  # the profiler is process-global; in-process re-runs
    # (library use, tests) must not inherit the previous run's stats

    # Graceful preemption: preemptible TPU workers get SIGTERM with a grace
    # window. Finish the in-flight video(s) — atomic writes + the idempotent
    # skip make a restarted worker resume exactly where this one stopped —
    # drop the rest, and exit 143. (The reference's only preemption story
    # was re-running the whole shuffled list, README.md:75-77.)
    import signal
    import threading
    stop = threading.Event()
    in_main = threading.current_thread() is threading.main_thread()
    prev_handler = None
    if in_main:
        def _on_sigterm(signo, frame):
            print("SIGTERM: finishing in-flight video(s), dropping the rest")
            stop.set()
        prev_handler = signal.signal(signal.SIGTERM, _on_sigterm)

    workers_arg = args.get("video_workers") or 1
    if workers_arg == "auto":  # sanity_check normalized/validated strings
        # decode threads beyond the core count just contend; beyond ~8 the
        # single device queue is the limiter anyway
        import os as _os
        workers_arg = max(1, min(8, (_os.cpu_count() or 1) // 2))
    workers = int(workers_arg)
    tally = {"done": 0, "skipped": 0, "error": 0, "quarantined": 0}
    # multi-family: the tally counts (video, family) units; this breaks
    # them out per family for the end-of-run summary
    fam_tally = {f: dict(tally) for f in families} if multi_mode else None
    videos_run = [0]  # videos that entered run_one (vs dropped by SIGTERM)
    tally_lock = threading.Lock()
    t_run = time.perf_counter()

    # Fault-tolerance runtime (utils/faults.py): categorized retries with
    # backoff + the decode degradation ladder per video, a per-video
    # deadline watchdog, and — for file sinks — the persistent failure
    # journal that quarantines known-poison inputs across restarts. The
    # print sink has no resume contract, so it keeps no journal.
    # (Multi-family runs carry one policy+journal PER FAMILY inside the
    # MultiExtractor instead — a quarantine is a per-family verdict.)
    from .utils.faults import FailureJournal, RetryPolicy
    policy = journal = None
    if not multi_mode:
        policy = RetryPolicy.from_config(args)
        journal = (FailureJournal(args.output_path)
                   if args.get("on_extraction", "print") != "print" else None)
    failures: List[dict] = []  # this run's terminal records (GIL-safe append)

    # Structured telemetry (telemetry=true): per-video span records in
    # {output_path}/_telemetry.jsonl, periodic _heartbeat_{host_id}.json,
    # and the _run.json manifest at exit. Off by default: every
    # instrumentation point below degrades to a no-op context manager /
    # one-global-read helper (docs/observability.md).
    from .telemetry import NOOP_SPAN
    recorder = None
    if bool(args.get("telemetry", False)):
        import socket
        from .config import _plain
        from .telemetry.recorder import TelemetryRecorder
        host_id = socket.gethostname()
        try:
            import jax
            host_id = f"p{jax.process_index()}-{host_id}"
        except Exception:
            pass
        if fleet_mode == "queue":
            # lease ownership + heartbeat files are keyed on host_id, and
            # queue workers may legitimately share one machine (tests,
            # smoke gates, over-subscribed hosts) — pid + a nonce keep
            # each worker's identity, claims dir and liveness file
            # distinct even for in-process sibling workers
            import os
            import uuid
            host_id = f"{host_id}-{os.getpid()}-{uuid.uuid4().hex[:4]}"
        run_config = (_plain(args) if not multi_mode else
                      {"feature_type": run_label,
                       "families": {f: _plain(a)
                                    for f, a in per_family.items()}})
        recorder = TelemetryRecorder(
            # multi: run-scoped artifacts live at the common output root
            # (per-family sinks are namespaced beneath it); spans carry
            # their own per-family feature_type
            out_root,
            run_config=run_config,
            feature_type=run_label,
            interval_s=float(args.get("metrics_interval_s") or 30.0),
            host_id=host_id,
            mon_baseline=mon_baseline,
        )

    # Alerting & flight recorder (alerts=true) + retained heartbeat
    # history (history=true): both ride the heartbeat tick as recorder
    # hooks, registered BEFORE start() so the t=0 heartbeat seeds the
    # windowed baselines. alerts=true implies history retention — the
    # burn-rate/spike rules diff retained samples. A firing rule appends
    # a transition to {out_root}/_alerts.jsonl and captures a black-box
    # bundle under _incidents/{alert_id}/ (telemetry/alerts.py;
    # docs/observability.md "Alerting & incident bundles").
    alert_engine = None
    if recorder is not None:
        if bool(args.get("history", False)) or bool(args.get("alerts",
                                                             False)):
            from .telemetry.history import HistoryWriter
            HistoryWriter(out_root, recorder.host_id).attach(recorder)
        if bool(args.get("alerts", False)):
            from .telemetry.alerts import AlertEngine
            alert_engine = AlertEngine(
                out_root, run_id=recorder.run_id).attach(recorder)
        # Storage lifecycle accounting (gc=true, gc.py): a heartbeat
        # "gc" section with per-plane/per-tenant byte usage (cached —
        # the tree walk refreshes at most every gc_interval_s) plus the
        # vft_gc_* gauges the disk_pressure alert rule projects from.
        # Accounting only: eviction is vft-gc's job (docs/storage.md).
        # gc=false (default) registers nothing — zero footprint.
        if bool(args.get("gc", False)):
            from .gc import GcConfig, GcMonitor
            GcMonitor(out_root, GcConfig.from_args(args)).attach(recorder)
        recorder.start()

    # Pipeline tracing (trace=true): a Chrome-trace timeline of the host
    # pipeline — every profiler.stage call, fan-out backpressure stall,
    # prefetch and retry wait — drained to {out_root}/_trace.json at exit
    # (telemetry/trace.py). Off by default: every trace helper is a
    # one-global-read no-op, the same discipline as telemetry=false.
    tracer = None
    if bool(args.get("trace", False)):
        from .telemetry.trace import TraceRecorder
        # fleet=queue workers co-own out_root: each writes its own
        # _trace_{host_id}.json (single-writer dirs keep _trace.json) —
        # otherwise the last worker to exit would overwrite every other
        # host's timeline, and vft-fleet --stitch needs them all
        tracer = TraceRecorder(
            out_root,
            host_id=(host_id if fleet_mode == "queue"
                     and recorder is not None else None)).start()

    # Roofline observatory (roofline=true, telemetry/roofline.py): XLA
    # cost cards per dispatched program + measured forward/h2d stage
    # seconds -> per-family effective TFLOPS, MFU vs the device peak
    # registry, and a compute/bandwidth/launch-overhead/host-bound
    # verdict, written to {out_root}/_roofline.json at exit (per-host in
    # fleet=queue dirs, like traces). Off by default: the dispatch hook
    # is one module-global read.
    rf_observer = None
    if bool(args.get("roofline", False)):
        from .telemetry.roofline import RooflineObserver
        rf_observer = RooflineObserver(
            out_root, default_family=run_label,
            run_id=(recorder.run_id if recorder is not None else None),
            host_id=(recorder.host_id if fleet_mode == "queue"
                     and recorder is not None else None)).start()

    # Parity observatory (parity=true, telemetry/parity.py): per-seam
    # numerics digests (decode -> transform -> backbone -> head) appended
    # to {out_root}/_parity.jsonl (per-host in fleet=queue dirs, like
    # traces). Off by default: every tap is one module-global read, and
    # the transform-seam wrapper is never even installed.
    parity_observer = None
    if bool(args.get("parity", False)):
        from .telemetry import parity as parity_mod
        parity_observer = parity_mod.ParityObserver(
            out_root,
            host_id=(recorder.host_id if fleet_mode == "queue"
                     and recorder is not None else None))
        parity_mod._set_active(parity_observer)

    # Work-stealing fleet queue (fleet=queue, parallel/queue.py): instead
    # of owning a fixed hash shard, this host claims videos one at a time
    # from the shared {out_root}/_queue/ by atomic rename, renews its
    # lease stamps from the heartbeat flusher thread (extra_sections
    # hook), and steals expired leases when idle — fleet makespan
    # approaches total_work/n_hosts instead of max(shard). sanity_check
    # guarantees recorder is live here (fleet=queue needs telemetry=true).
    work_queue = None
    if fleet_mode == "queue":
        if recorder is None:  # library callers can bypass sanity_check
            raise ValueError("fleet=queue needs telemetry=true: the "
                             "heartbeat thread renews the work-item leases")
        from .parallel.queue import WorkQueue
        work_queue = WorkQueue(
            out_root, host_id=host_id, run_id=recorder.run_id,
            lease_s=float(args.get("fleet_lease_s") or 60.0),
            max_reclaims=int(args.get("fleet_max_reclaims") or 3),
            journal=(journal if not multi_mode else None),
            staging_retention_s=(
                float(args["gc_staging_retention_s"])
                if args.get("gc_staging_retention_s") is not None
                else None))
        recorder.extra_sections["fleet"] = work_queue.heartbeat_section
        # canary warm fast path (compile_cache.py): a joining host whose
        # compile-cache fingerprint fully hit has no cold-compile jitter
        # for the canary timing bands to absorb — the gate tightens, and
        # the heartbeat fleet section records canary_warm=true
        work_queue.canary_warm = bool(cc_entry is not None
                                      and cc_entry.warm_at_attach)
        seeded = work_queue.seed(video_paths)
        print(f"fleet: queue mode — seeded {seeded} new item(s) into "
              f"{work_queue.root} as {host_id}")

    # Output health (health=true): per-(video, family) feature digests at
    # the sink boundary, appended to each family's {output_path}/
    # _health.jsonl, with NaN/Inf outputs quarantined via the faults
    # taxonomy instead of written (telemetry/health.py). The gate itself
    # lives in BaseExtractor.action_on_extraction — this flag only drives
    # the end-of-run pointer below.
    health_on = (any(bool(a.get("health", False))
                     for a in per_family.values())
                 if multi_mode else bool(args.get("health", False)))

    def run_one(video_path: str) -> str:
        """Extract one video; the returned status feeds the fleet queue's
        done marker ('dropped' = preempted before starting, the queue
        releases the claim instead of completing it)."""
        if stop.is_set():
            return "dropped"
        with tally_lock:
            videos_run[0] += 1
        if multi is not None:
            statuses = multi.run_video(video_path, recorder=recorder,
                                       failures=failures)
            with tally_lock:
                for fam, status in statuses.items():
                    tally[status] += 1
                    fam_tally[fam][status] += 1
            # one done marker per video: the worst per-family verdict
            for agg in ("error", "quarantined", "done"):
                if agg in statuses.values():
                    return agg
            return "skipped"
        span_cm = (recorder.video_span(video_path)
                   if recorder is not None else NOOP_SPAN)
        with span_cm as span:
            status = safe_extract(extractor._extract, video_path,
                                  policy=policy, journal=journal,
                                  decode_mode=extractor.video_decode,
                                  on_terminal_failure=failures.append)
            span.annotate(status=status)
        with tally_lock:
            tally[status] += 1
        return status

    def canary_extract(video_path: str, canary_dir: str):
        """Joining-host canary (fleet_canary=true): re-extract one
        already-completed video into a throwaway dir with a FRESH
        extractor — cache off (the gate must recompute, not re-serve)
        and health on (compare_runs digest bands need digests)."""
        from .config import Config, _plain
        c_args = Config(_plain(args))
        c_args.output_path = canary_dir
        c_args.cache = False
        c_args.health = True
        c_ext = get_extractor_cls(args.feature_type)(c_args)
        t0 = time.perf_counter()
        status = safe_extract(c_ext._extract, video_path, policy=policy,
                              journal=None, decode_mode=c_ext.video_decode)
        return status, time.perf_counter() - t0

    try:
        with TraceCapture(args.get("profile_trace_dir")):
            if work_queue is not None:
                if bool(args.get("fleet_canary", False)):
                    if multi_mode:
                        print("fleet canary: multi-family runs are not "
                              "canary-gated yet — claims open (per-family "
                              "health gates still apply)")
                    else:
                        ok, lines = work_queue.canary_gate(canary_extract)
                        print("\n".join(lines))
                        if not ok:
                            raise SystemExit(
                                "fleet canary: FAILED — this host is gated "
                                "out of the queue (digest or timing drift; "
                                "verdict in "
                                f"{work_queue.root}/canary/, docs/fleet.md)")
                # claim -> extract -> complete until the queue is drained
                # FLEET-wide; the bar tracks this host's completions
                # against the full corpus (other hosts take the rest)
                pbar = tqdm(total=len(video_paths), desc="fleet")
                try:
                    work_queue.drain(
                        run_one, workers=workers, stop=stop,
                        on_complete=lambda rec, status: pbar.update(1))
                finally:
                    pbar.close()
                    # escaped-exception / preemption safety net: hand any
                    # still-held claims back unbumped so another host
                    # re-dispatches them immediately
                    work_queue.release_all()
            elif workers <= 1:
                for video_path in tqdm(video_paths):
                    if stop.is_set():
                        break
                    run_one(video_path)
            else:
                # Cross-video pipelining: the host side (cv2 decode + PIL
                # transforms) of up to `video_workers` videos runs on
                # concurrent threads feeding the single device queue — while
                # one video's batch computes, another video decodes. cv2/PIL
                # release the GIL; each video's FeatureStream keeps its own
                # submit order, and per-video error isolation (safe_extract)
                # is unchanged. The reference's only cross-video parallelism
                # was whole extra processes per GPU (reference README.md:
                # 70-84).
                from concurrent.futures import (ThreadPoolExecutor,
                                                as_completed)
                with ThreadPoolExecutor(max_workers=workers,
                                        thread_name_prefix="vft-video") as pool:
                    futures = [pool.submit(run_one, vp)
                               for vp in video_paths]
                    try:
                        # completion order, not submission order: with
                        # pool.map the bar (the operator's liveness read)
                        # stalls on the slowest head-of-line video while
                        # finished ones pile up uncounted behind it.
                        # result() re-raises a worker's escaped exception,
                        # as iterating pool.map's results did. SIGTERM
                        # semantics are unchanged: queued videos still run
                        # run_one, which drops them via the stop flag.
                        for fut in tqdm(as_completed(futures),
                                        total=len(futures)):
                            fut.result()
                    except BaseException:
                        # drop the not-yet-started videos; in-flight ones
                        # finish (their outputs stay valid thanks to atomic
                        # writes + resume-on-restart)
                        pool.shutdown(cancel_futures=True)
                        raise
    finally:
        # prev_handler is None when a C-level handler was installed before
        # us; signal.signal() can't restore those (TypeError)
        if in_main and prev_handler is not None:
            signal.signal(signal.SIGTERM, prev_handler)
        if recorder is not None:
            by_cat: dict = {}
            for rec in failures:
                cat = rec.get("category") or "?"
                by_cat[cat] = by_cat.get(cat, 0) + 1
            # close() in the finally: a SIGTERM/KeyboardInterrupt exit must
            # still leave a manifest + final heartbeat behind — that partial
            # record is exactly what an operator debugs the abort with
            rf_summary = None
            if rf_observer is not None:
                try:
                    # summarized BEFORE the recorder closes so the manifest
                    # (and the final heartbeat's live snapshot) carry the
                    # end-of-run MFU/verdicts
                    rf_summary = rf_observer.summary(resolve_peak=True)
                except Exception:
                    rf_summary = None
            recorder.close(tally=dict(tally),
                           wall_s=time.perf_counter() - t_run,
                           failure_tallies=by_cat,
                           roofline=rf_summary)
        if rf_observer is not None:
            # after the recorder: observer.close restores the stage hook
            # only if still its own, and writes _roofline.json atomically
            rf_observer.close()
        if tracer is not None:
            # likewise in the finally: an aborted run's partial timeline is
            # still a complete, loadable trace file (atomic temp+rename)
            tracer.close()
        if parity_observer is not None:
            # appends are already durable (O_APPEND); close just detaches
            # the module global so in-process callers don't inherit taps
            from .telemetry import parity as parity_mod
            if parity_mod.active() is parity_observer:
                parity_mod._set_active(None)
            parity_observer.close()
        if inject_plan is not None:
            # the chaos run's record of exactly what it injected (the
            # counters land in the manifest metrics dump too)
            print(inject_plan.summary())
        inject.disarm()  # in-process callers must not inherit the plan
        # seal the compile-cache entry even on an aborted run: every
        # executable XLA finished writing is complete (its own write is
        # atomic), and sealing it saves the next host that compile
        compile_cache.seal_active()

    elapsed = time.perf_counter() - t_run
    n_run = sum(tally.values())
    if multi_mode:
        summary = (f"{videos_run[0]}/{len(video_paths)} videos x "
                   f"{len(families)} families in {elapsed:.1f}s: "
                   f"{tally['done']} extracted, {tally['skipped']} already "
                   f"done, {tally['error']} failed")
    else:
        summary = (f"{n_run}/{len(video_paths)} videos in {elapsed:.1f}s: "
                   f"{tally['done']} extracted, {tally['skipped']} already "
                   f"done, {tally['error']} failed")
    if tally["quarantined"]:
        summary += f", {tally['quarantined']} quarantined"
    if failures:
        by_cat: dict = {}
        for rec in failures:
            cat = rec.get("category") or "?"
            by_cat[cat] = by_cat.get(cat, 0) + 1
        summary += (" [" + ", ".join(f"{k}={v}"
                                     for k, v in sorted(by_cat.items()))
                    + "]")
    if tally["done"]:
        unit = "extractions/s" if multi_mode else "videos/s"
        summary += f" ({tally['done'] / elapsed:.2f} {unit})"
    print(summary)
    if multi_mode:
        for fam in families:
            ft = fam_tally[fam]
            line = (f"  {fam}: {ft['done']} extracted, {ft['skipped']} "
                    f"already done, {ft['error']} failed")
            if ft["quarantined"]:
                line += f", {ft['quarantined']} quarantined"
            print(line)
    if failures and multi_mode:
        for fam in sorted({rec.get("family") for rec in failures
                           if rec.get("family")}):
            j = multi.journals.get(fam)
            if j is not None:
                print(f"failure journal ({fam}): {j.path} "
                      "(retry_failed=true re-runs quarantined videos)")
    if failures and journal is not None:
        print(f"failure journal: {journal.path} (retry_failed=true re-runs "
              "quarantined videos)")
    if recorder is not None:
        print(f"telemetry: {recorder.manifest_path} + {recorder.spans_path} "
              f"(render with scripts/telemetry_report.py "
              f"{out_root})")
    if alert_engine is not None:
        s = alert_engine.heartbeat_section()
        print(f"alerts: {s.get('firing', 0)} firing / "
              f"{s.get('pending', 0)} pending at exit — journal in "
              f"{out_root}/_alerts.jsonl, incident bundles in "
              f"{out_root}/_incidents/ (render with vft-alert {out_root})")
    if tracer is not None:
        print(f"trace: {tracer.trace_path} (render with "
              f"scripts/trace_report.py {out_root}, or open in "
              "https://ui.perfetto.dev)")
    if rf_observer is not None:
        print(f"roofline: {rf_observer.path} (render with vft-roofline "
              f"{out_root})")
    if parity_observer is not None:
        print(f"parity: per-seam numerics digests in {parity_observer.path} "
              f"(render with vft-parity {out_root}; certify flips with "
              "vft-parity certify)")
    if health_on:
        from .telemetry.health import HEALTH_FILENAME
        print(f"health: per-(video, family) feature digests in "
              f"{{output_path}}/{HEALTH_FILENAME} under {out_root} "
              f"(diff two runs with scripts/compare_runs.py)")
    if profiler.enabled:
        print(profiler.summary(f"profile: {run_label} x "
                               f"{len(video_paths)} videos"))
    if stop.is_set():
        raise SystemExit(143)  # conventional SIGTERM exit; resume = re-run
    if verbose:
        print(f"Yay! Done! The results are in {args.output_path}")


if __name__ == "__main__":
    main()
