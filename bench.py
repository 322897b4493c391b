"""Benchmark: every model family's device throughput on the available
accelerator, plus the sustained real-pipeline number.

Prints ONE JSON line. Top-level fields carry the R(2+1)D-18 headline (the
shape the driver has recorded since round 1); a ``metrics`` array carries
both north-star configs (BASELINE.md: "clips/sec/chip for R(2+1)D and
I3D-RGB+Flow"), one device-throughput row per remaining family (resnet50,
CLIP ViT-B/32, s3d, vggish, raft, pwc — round-4 coverage), and the
decode->device->sink pipeline rate:

  {"metric": "...r2plus1d_18...", "value": N, "unit": "clips/sec/chip",
   "vs_baseline": N, "metrics": [...]}

The reference publishes no throughput numbers (BASELINE.md), so baselines
are measured: the same architectures run in torch (the reference's engine)
on this host's CPU exactly like the reference's serial per-slice loops.
``vs_baseline`` is ours/theirs on identical work units; every row carries a
``baseline`` field naming that denominator explicitly ("x torch-cpu-1core"
— NOT a GPU ratio; BASELINE.md's analytic-A100 section does the
absolute-hardware accounting). PWC's torch twin cannot run here at all
(the reference's correlation op is a CUDA-only CuPy kernel,
/root/reference/models/pwc/pwc_src/correlation.py), so its ratio is null
by construction.

R(2+1)D config: steady-state jitted forward, maximum-throughput ingest
(``ingest=yuv420``: packed I420 uint8 clips, 1.5 bytes/pixel, colorspace
fused on device — ops/colorspace.py), bfloat16, B=128 clips per step.

I3D config: the full reference work unit (extract_i3d.py:140-169) — 64+1 RGB
frames at 224px -> RAFT flow on 64 consecutive pairs (20 GRU iterations
each) -> ToUInt8 quantize -> I3D-RGB + I3D-Flow forwards, all on device.

Measurement notes (this file predates PR 0; ROADMAP S0 rebuilds it around
cells, medians and a device check — see ISSUE 21 for the hazards it still
carries: every phase of main() is try/except -> WARNING and the run exits 0):
  - completion is fenced with a D2H read of the last output (`settle`,
    parallel/mesh.py);
  - input batches are staged on device before the timed loop, so these rows
    time the device program alone. In deployment the pipeline streams H2D
    asynchronously under compute (FeatureStream); the end-to-end row is
    bench_pipeline;
  - each row is the best of TRIALS; torch trials additionally run an
    adaptive iteration count (>= MIN_TRIAL_SECONDS wall each) so the CPU
    side is not a 3-sample coin flip.
"""
import json
import os
import sys
import time

import numpy as np

#: what every vs_baseline ratio divides by (VERDICT r3 #5: the number must
#: name its denominator — it is NOT a GPU comparison)
BASELINE_DESC = ("x torch-cpu-1core: same architecture + work unit in "
                 "torch (the reference's engine) on one CPU core of this "
                 "host; absolute-hardware accounting in BASELINE.md")

CLIP = (16, 112, 112, 3)  # stack, H, W, C
# measured sweet spot on v5e for the current yuv420+bf16 program (round-2
# sweep): 64 -> 972, 96 -> 1144, 128 -> 1471, 192 -> 1136 (tiling dip),
# 256 -> 1429 clips/s. The round-1 "B=128 flat" note predates this program.
BATCH = 128
I3D_STACK = 64      # the reference's default stack (BASELINE.json flagship)
I3D_SIDE = 224
WARMUP = 5
ITERS = 30
TRIALS = 3  # the best trial is reported (S0: median and quartiles)
MIN_TRIAL_SECONDS = 1.5  # torch baselines: floor per timed trial


def _enable_cache_off_cpu() -> None:
    import jax
    if jax.default_backend() != "cpu":
        # persistent compile cache (safe off-CPU — see cli.py): repeat bench
        # runs skip the multi-minute XLA compiles and measure steady state
        from video_features_tpu.cli import _enable_compilation_cache
        _enable_compilation_cache({"device": "auto"})


def bench_ours(batch: int = BATCH) -> float:
    import jax
    import jax.numpy as jnp
    _enable_cache_off_cpu()
    from video_features_tpu.models.r21d import R2Plus1D

    from video_features_tpu.extractors.r21d import _device_forward_yuv420
    from video_features_tpu.ops.colorspace import packed_size
    from video_features_tpu.parallel.mesh import cast_floating, settle

    model = R2Plus1D("r2plus1d_18_16_kinetics")
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4, 112, 112, 3)))["params"]
    # bf16 params + bf16 activations: with f32 params flax would promote every
    # conv back to f32, halving MXU throughput (parallel/mesh.py cast_floating)
    params = cast_floating(params, jnp.bfloat16)

    @jax.jit
    def forward(p, packed_u8):
        return _device_forward_yuv420(model, jnp.bfloat16, p, packed_u8)

    rng = np.random.default_rng(0)
    wire = (batch, CLIP[0], packed_size(CLIP[1], CLIP[2]))
    batches = [jax.device_put(rng.integers(0, 255, size=wire, dtype=np.uint8))
               for _ in range(2)]
    _record_cost(f"r21d_b{batch}", forward, (params, batches[0]))
    settle(forward(params, batches[0]))  # compile
    for _ in range(WARMUP):
        settle(forward(params, batches[1]))
    best = 0.0
    for _ in range(TRIALS):  # best-of
        t0 = time.perf_counter()
        for i in range(ITERS):
            out = forward(params, batches[i % 2])
        settle(out)
        dt = time.perf_counter() - t0
        best = max(best, batch * ITERS / dt)
    return best


def bench_torch_reference() -> float:
    """Reference-style serial batch=1 torch forward on this host's CPU."""
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent / "tests"))
    import torch
    from torch_oracles import TorchR2Plus1D

    model = TorchR2Plus1D(layers=(2, 2, 2, 2)).eval()
    x = torch.randn(1, 3, *CLIP[:3])
    best = 0.0
    with torch.no_grad():
        model(x)  # warmup
        for _ in range(TRIALS):  # same best-of selection as bench_ours
            n = 0
            t0 = time.perf_counter()
            # adaptive count: at least MIN_TRIAL_SECONDS of wall per trial
            while True:
                model(x)
                n += 1
                dt = time.perf_counter() - t0
                if dt >= MIN_TRIAL_SECONDS and n >= 3:
                    break
            best = max(best, n / dt)
    return best


# ---- roofline fields on every device row (ISSUE 12) ----------------------
#
# Each device bench registers its jitted step's XLA cost card here
# (telemetry/roofline.py program_cost — the same lowered.cost_analysis()
# arithmetic behind the old hand table in docs/performance.md), and
# main() stamps mfu/effective_tflops onto the row from the measured rate,
# so bench_history's regression gate guards device EFFICIENCY, not just
# throughput: a change that kept clips/s by burning 2x the FLOPs — or
# halved MFU on a faster chip — flags.

PROGRAM_COSTS = {}


def _record_cost(key: str, step, args) -> None:
    """Capture one jitted step's {flops, bytes} per dispatch under
    ``key``; never fails the bench (cost is accounting, not the metric)."""
    try:
        from video_features_tpu.telemetry.roofline import program_cost
        PROGRAM_COSTS[key] = program_cost(step, *args)
    except Exception as e:
        print(f"WARNING: cost capture failed for {key}: "
              f"{type(e).__name__}: {e}", file=sys.stderr)


_PEAK_CACHE = []


def _device_peak():
    """This process's MFU denominator (telemetry/roofline.py
    peak_for_device: registry -> cached microbench -> microbench),
    resolved once per bench run."""
    if not _PEAK_CACHE:
        try:
            from video_features_tpu.telemetry.roofline import peak_for_device
            _PEAK_CACHE.append(peak_for_device())
        except Exception as e:
            print(f"WARNING: device peak resolution failed: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            _PEAK_CACHE.append(None)
    return _PEAK_CACHE[0]


def _roofline_fields(key: str, units_per_s, units_per_dispatch: int) -> dict:
    """``{effective_tflops, mfu}`` for a row whose jitted step was
    cost-registered under ``key`` — empty when capture failed, so a row
    never lies with zeros."""
    c = PROGRAM_COSTS.get(key)
    if not c or not c.get("flops") or not units_per_s:
        return {}
    eff = units_per_s * (c["flops"] / units_per_dispatch) / 1e12
    out = {"effective_tflops": round(eff, 4)}
    peak = _device_peak()
    if peak and peak.get("peak_tflops"):
        out["mfu"] = round(eff / peak["peak_tflops"], 4)
    return out


def _device_rate(step, args_list, units_per_iter, iters: int,
                 warmup: int = 3, trials: int = TRIALS) -> float:
    """Best-of-trials units/sec for a jitted step over pre-staged device
    batches (see the module docstring's measurement notes: D2H-fenced via
    ``settle``, inputs resident before the timed loop). Single-variant
    case of :func:`_device_rate_ab` so the timing discipline lives once."""
    return _device_rate_ab([(step, args_list)], units_per_iter, iters,
                           warmup, trials)[0]


def _device_rate_ab(variants, units_per_iter, iters: int,
                    warmup: int = 3, trials: int = TRIALS) -> list:
    """Interleaved twin of :func:`_device_rate` for VARIANT COMPARISONS.

    ``variants`` is a list of (step, args_list); every trial round times
    ALL variants back-to-back and each variant keeps its best trial:
    cross-variant claims come from alternating timings in ONE process
    (docs/performance.md), so that drift over a run falls on every variant
    alike. Returns best units/sec per variant, same order.
    """
    from video_features_tpu.parallel.mesh import settle
    for step, args_list in variants:
        settle(step(*args_list[0]))  # compile
        for _ in range(warmup):
            settle(step(*args_list[1 % len(args_list)]))
    best = [0.0] * len(variants)
    for _ in range(trials):
        for vi, (step, args_list) in enumerate(variants):
            t0 = time.perf_counter()
            for i in range(iters):
                out = step(*args_list[i % len(args_list)])
            settle(out)
            best[vi] = max(best[vi],
                           units_per_iter * iters
                           / (time.perf_counter() - t0))
    return best


def _torch_seconds_per_call(fn, trials: int = TRIALS) -> float:
    """Best-of-TRIALS seconds/call; each trial repeats fn until the
    adaptive wall floor so short calls are not a 3-sample coin flip (heavy
    calls exceed the floor in one repeat — their single-sample noise is
    proportionally small)."""
    import torch
    best = float("inf")
    with torch.no_grad():
        for _ in range(trials):
            n = 0
            t0 = time.perf_counter()
            while True:
                fn()
                n += 1
                dt = time.perf_counter() - t0
                if dt >= MIN_TRIAL_SECONDS:
                    break
            best = min(best, dt / n)
    return best


def bench_i3d_ours(stack: int = I3D_STACK, iters: int = 10,
                   warmup: int = 3, raft_bf16: bool = False,
                   n_stacks: int = 4) -> float:
    """I3D RGB+Flow(RAFT) stacks/sec, the full on-device two-stream chain
    in the production composition: ``n_stacks`` stacks' pair batches fused
    into ONE RAFT forward (extractors/i3d_flow.py _stacks_per_forward
    auto-picks 4 at this geometry) with the fused lookup+convc1 kernel
    (kernels/corr_lookup.py corr_lookup_proj, the TPU default).

    ``raft_bf16`` runs the flow model in its plumbed bfloat16 mode
    (models/raft.py RAFT.dtype: conv stacks bf16, pyramid/lookup/coords
    f32) — the extractor's ``precision=bfloat16`` configuration. Flow
    drift is ~0.1 px, under the flow stream's ToUInt8 quantization step
    (~0.16), so it is a legitimate production mode for this chain."""
    import jax
    import jax.numpy as jnp
    _enable_cache_off_cpu()
    from video_features_tpu.extractors.i3d import _i3d_forward
    from video_features_tpu.extractors.i3d_flow import _crop_quantize
    from video_features_tpu.models import i3d as i3d_m, raft as raft_m
    from video_features_tpu.parallel.mesh import cast_floating

    model = i3d_m.I3D(num_classes=400)
    raft_dtype = jnp.bfloat16 if raft_bf16 else jnp.float32
    raft = raft_m.RAFT(iters=raft_m.ITERS, dtype=raft_dtype)
    i3d_rgb = cast_floating(i3d_m.init_params("rgb"), jnp.bfloat16)
    i3d_flow = cast_floating(i3d_m.init_params("flow"), jnp.bfloat16)
    raft_p = cast_floating(raft_m.init_params(), raft_dtype)

    @jax.jit
    def step(rp, pr, pf, stacks_u8):
        # stacks_u8: (S, stack+1, H, W, 3) uint8 — the extractor's own
        # device functions composed exactly like ExtractI3D.dispatch_stream
        # + FlowStream._device_flow (S stacks -> one S*stack pair batch)
        s = stacks_u8.shape[0]
        pairs = jnp.stack([stacks_u8[:, :-1], stacks_u8[:, 1:]], axis=2)
        pairs = pairs.reshape((s * stack,) + pairs.shape[2:])
        flow = raft_m.padded_flow(raft, rp, pairs.astype(jnp.float32))[0]
        quant = _crop_quantize(flow, I3D_SIDE)
        quant = quant.reshape((s, stack) + quant.shape[1:])
        rgb_feat = _i3d_forward(model, jnp.bfloat16, True, pr,
                                stacks_u8[:, :-1].astype(jnp.float32))
        flow_feat = _i3d_forward(model, jnp.bfloat16, True, pf, quant)
        return rgb_feat, flow_feat

    rng = np.random.default_rng(0)
    stacks = [jax.device_put(rng.integers(
        0, 255, size=(n_stacks, stack + 1, I3D_SIDE, I3D_SIDE, 3),
        dtype=np.uint8)) for _ in range(2)]
    args = [(raft_p, i3d_rgb, i3d_flow, s) for s in stacks]
    _record_cost(f"i3d_raft{'_bf16' if raft_bf16 else ''}", step, args[0])
    return _device_rate(step, args, n_stacks, iters, warmup)


def bench_i3d_pwc_ours(stack: int = I3D_STACK, iters: int = 10,
                       warmup: int = 3, n_stacks: int = 4) -> float:
    """I3D RGB+Flow(PWC) stacks/sec — the DEFAULT i3d configuration
    (configs/i3d.yml flow_type: pwc, matching the reference default) in
    its production bf16 mode (models/pwc.py PWCNet.dtype: conv stacks and
    cost volumes bf16; flow tensors, warp grid and flow heads f32 — drift
    0.015 px max, an order under the flow stream's ToUInt8 quantization).

    Round-5 interleaved A/B (medians of 4
    alternating rounds on v5e): raft-s4f 6.28 / pwc-f32 5.86 / pwc-bf16
    6.78 / x2 stacks 11.33 / x4 stacks 12.08 / x8 10.90 stacks/s — so
    n_stacks=4 (what _pwc_stacks_per_forward auto-picks at this geometry)
    and the default flow_type stays pwc, now measured rather than
    inherited."""
    import jax
    import jax.numpy as jnp
    _enable_cache_off_cpu()
    from video_features_tpu.extractors.i3d import _i3d_forward
    from video_features_tpu.extractors.i3d_flow import _crop_quantize
    from video_features_tpu.models import i3d as i3d_m, pwc as pwc_m
    from video_features_tpu.parallel.mesh import cast_floating

    model = i3d_m.I3D(num_classes=400)
    pwc = pwc_m.PWCNet(dtype=jnp.bfloat16)
    i3d_rgb = cast_floating(i3d_m.init_params("rgb"), jnp.bfloat16)
    i3d_flow = cast_floating(i3d_m.init_params("flow"), jnp.bfloat16)
    pwc_p = pwc_m.init_params()

    @jax.jit
    def step(pp, pr, pf, stacks_u8):
        s = stacks_u8.shape[0]
        pairs = jnp.stack([stacks_u8[:, :-1], stacks_u8[:, 1:]], axis=2)
        pairs = pairs.reshape((s * stack,) + pairs.shape[2:])
        x = pairs.astype(jnp.float32)
        flow = pwc.apply({"params": pp}, x[:, 0], x[:, 1])
        quant = _crop_quantize(flow, I3D_SIDE)
        quant = quant.reshape((s, stack) + quant.shape[1:])
        rgb_feat = _i3d_forward(model, jnp.bfloat16, True, pr,
                                stacks_u8[:, :-1].astype(jnp.float32))
        flow_feat = _i3d_forward(model, jnp.bfloat16, True, pf, quant)
        return rgb_feat, flow_feat

    rng = np.random.default_rng(0)
    stacks = [jax.device_put(rng.integers(
        0, 255, size=(n_stacks, stack + 1, I3D_SIDE, I3D_SIDE, 3),
        dtype=np.uint8)) for _ in range(2)]
    args = [(pwc_p, i3d_rgb, i3d_flow, s) for s in stacks]
    _record_cost("i3d_pwc", step, args[0])
    return _device_rate(step, args, n_stacks, iters, warmup)


def bench_pipeline(n_copies: int = 8) -> dict:
    """Sustained REAL-pipeline throughput: decode -> transform -> device ->
    sink, through the actual CLI driver, on ``n_copies`` of the vendored
    sample video — the deliverable number next to the device-only steady
    state (which assumes decode keeps up). Uses the RECORDED production
    configuration: yuv420 ingest, bf16, ClipPacker cross-video batching at
    the B=128 sweet spot, video_workers=auto. Runs with ``trace=true`` and
    publishes the per-stage decode/transform/h2d/device/write breakdown +
    X-bound verdict from the trace (scripts/trace_report.py stage_summary),
    so every round's sustained number carries its own roofline diagnosis —
    on a few-core host this is decode-bound, and the stage split proves by
    how much (docs/performance.md 'The host roofline, demolished by
    stages')."""
    import shutil
    import sys as _sys
    import tempfile
    from pathlib import Path

    sample = Path(__file__).parent / "tests" / "assets" / "v_synth_sample.mp4"
    if not sample.exists():
        sample = Path("/root/reference/sample/v_GGSY1Qvo990.mp4")
    if not sample.exists():
        raise FileNotFoundError("no sample video for the pipeline bench")
    import contextlib
    from video_features_tpu.cli import main as cli_main
    with tempfile.TemporaryDirectory(prefix="vft_bench_pipe_") as td:
        vids = []
        for i in range(n_copies):
            dst = Path(td) / f"sample_copy{i}.mp4"
            shutil.copy(sample, dst)
            vids.append(str(dst))
        t0 = time.perf_counter()
        # the CLI prints its tally to stdout; bench.py's stdout contract is
        # ONE JSON line (the driver parses it), so route it to stderr
        with contextlib.redirect_stdout(_sys.stderr):
            cli_main([
                "feature_type=r21d", "precision=bfloat16", "ingest=yuv420",
                "clip_batch_size=128", "cross_video_batching=true",
                "video_workers=auto", "allow_random_weights=true",
                "trace=true",
                "on_extraction=save_numpy", f"output_path={td}/out",
                f"tmp_path={td}/tmp",
                "video_paths=[" + ",".join(vids) + "]",
            ])
        wall = time.perf_counter() - t0
        outputs = list(Path(td, "out").rglob("*_r21d.npy"))
        clips = sum(np.load(p).shape[0] for p in outputs)
        stages = None
        try:
            sys.path.insert(0, str(Path(__file__).parent / "scripts"))
            import trace_report
            traces = sorted(Path(td, "out").rglob(
                trace_report.TRACE_FILENAME))
            if traces:
                stages = trace_report.stage_summary(str(traces[0].parent))
        except BaseException as e:  # breakdown is telemetry, not the metric
            print(f"WARNING: pipeline stage breakdown failed: "
                  f"{type(e).__name__}: {e}", file=_sys.stderr)
    if len(outputs) < n_copies:
        # cli_main tallies per-video failures and returns normally; a bench
        # over identical healthy copies must complete ALL of them — anything
        # less would publish an inflated videos/s (n_copies / wall) for work
        # that partly failed. Route it to the caller's warning path instead.
        raise RuntimeError(
            f"pipeline bench: only {len(outputs)}/{n_copies} videos "
            "produced features — failed runs must not publish throughput")
    result = {"videos_per_s": n_copies / wall, "clips_per_s": clips / wall,
              "clips": clips, "wall_s": wall}
    if stages:
        result["stages"] = stages
    return result


def bench_shared_decode(families=("resnet", "clip", "s3d"),
                        n_copies: int = 4) -> dict:
    """Multi-family sharing ratio: N sequential single-family CLI runs
    (N full decode passes) vs ONE shared-decode run of the same families
    over the same corpus (parallel/fanout.py), fresh output dirs, each
    variant warmed untimed first. The ratio is recorded per bench round
    so decode-bound regressions in the fan-out path show up next to the
    device numbers; `scripts/throughput.py --families a,b` runs the
    longer interleaved-median version of the same A/B."""
    import contextlib
    import shutil
    import sys as _sys
    import tempfile
    from pathlib import Path

    sample = Path(__file__).parent / "tests" / "assets" / "v_synth_sample.mp4"
    if not sample.exists():
        sample = Path("/root/reference/sample/v_GGSY1Qvo990.mp4")
    if not sample.exists():
        raise FileNotFoundError("no sample video for the shared-decode bench")
    from video_features_tpu.cli import main as cli_main
    base = ["allow_random_weights=true", "on_extraction=save_numpy",
            "extraction_fps=4", "batch_size=32"]
    with tempfile.TemporaryDirectory(prefix="vft_bench_share_") as td:
        vids = []
        for i in range(n_copies):
            dst = Path(td) / f"sample_share{i}.mp4"
            shutil.copy(sample, dst)
            vids.append(str(dst))

        def run(feature_type: str, out: str, videos) -> float:
            argv = [f"feature_type={feature_type}", f"output_path={td}/{out}",
                    f"tmp_path={td}/tmp",
                    "video_paths=[" + ",".join(videos) + "]"] + base
            t0 = time.perf_counter()
            with contextlib.redirect_stdout(_sys.stderr):
                cli_main(argv)
            return time.perf_counter() - t0

        for fam in families:  # untimed warmups (weights, compiles, cache)
            run(fam, f"warm_{fam}", vids[:1])
        run(",".join(families), "warm_multi", vids[:1])
        seq = sum(run(fam, f"seq_{fam}", vids) for fam in families)
        shared = run(",".join(families), "shared", vids)
    return {"families": list(families), "n_copies": n_copies,
            "sequential_s": round(seq, 2), "shared_s": round(shared, 2),
            "sharing_ratio": round(seq / shared, 2)}


def bench_trace_overhead(families=("resnet", "clip", "s3d"),
                         n_copies: int = 2) -> dict:
    """Wall-clock cost of trace=true (telemetry/trace.py) on the shared-
    decode smoke corpus: the SAME multi-family CLI run, warmed untimed,
    then timed with trace=false and trace=true into fresh output dirs.
    The ratio is recorded per round so instrumentation creep on the hot
    loops (per-frame stage spans, fan-out backpressure accounting) shows
    up next to the numbers it would tax; the acceptance bar is <= 1.05x."""
    import contextlib
    import shutil
    import sys as _sys
    import tempfile
    from pathlib import Path

    sample = Path(__file__).parent / "tests" / "assets" / "v_synth_sample.mp4"
    if not sample.exists():
        sample = Path("/root/reference/sample/v_GGSY1Qvo990.mp4")
    if not sample.exists():
        raise FileNotFoundError("no sample video for the trace bench")
    from video_features_tpu.cli import main as cli_main
    base = ["allow_random_weights=true", "on_extraction=save_numpy",
            "extraction_fps=4", "batch_size=32"]
    with tempfile.TemporaryDirectory(prefix="vft_bench_trace_") as td:
        vids = []
        for i in range(n_copies):
            dst = Path(td) / f"sample_trace{i}.mp4"
            shutil.copy(sample, dst)
            vids.append(str(dst))

        def run(out: str, extra) -> float:
            argv = [f"feature_type={','.join(families)}",
                    f"output_path={td}/{out}", f"tmp_path={td}/tmp",
                    "video_paths=[" + ",".join(vids) + "]"] + base + extra
            t0 = time.perf_counter()
            with contextlib.redirect_stdout(_sys.stderr):
                cli_main(argv)
            return time.perf_counter() - t0

        run("warm", [])  # weights, compiles, persistent cache
        off = run("off", ["trace=false"])
        on = run("on", ["trace=true"])
    return {"families": list(families), "n_copies": n_copies,
            "off_s": round(off, 2), "on_s": round(on, 2),
            "overhead_ratio": round(on / off, 3)}


def bench_health_overhead(families=("resnet", "clip", "s3d"),
                          n_copies: int = 2) -> dict:
    """Wall-clock cost of health=true (telemetry/health.py) on the same
    smoke corpus as bench_trace_overhead: the multi-family CLI run,
    warmed untimed, then timed with health=false and health=true into
    fresh output dirs. The digests (O(n) reductions + one sha256 per
    feature tensor, at the sink boundary) are the instrumented path; the
    acceptance bar is <= 1.05x, tracked per round like the trace ratio."""
    import contextlib
    import shutil
    import sys as _sys
    import tempfile
    from pathlib import Path

    sample = Path(__file__).parent / "tests" / "assets" / "v_synth_sample.mp4"
    if not sample.exists():
        sample = Path("/root/reference/sample/v_GGSY1Qvo990.mp4")
    if not sample.exists():
        raise FileNotFoundError("no sample video for the health bench")
    from video_features_tpu.cli import main as cli_main
    base = ["allow_random_weights=true", "on_extraction=save_numpy",
            "extraction_fps=4", "batch_size=32"]
    with tempfile.TemporaryDirectory(prefix="vft_bench_health_") as td:
        vids = []
        for i in range(n_copies):
            dst = Path(td) / f"sample_health{i}.mp4"
            shutil.copy(sample, dst)
            vids.append(str(dst))

        def run(out: str, extra) -> float:
            argv = [f"feature_type={','.join(families)}",
                    f"output_path={td}/{out}", f"tmp_path={td}/tmp",
                    "video_paths=[" + ",".join(vids) + "]"] + base + extra
            t0 = time.perf_counter()
            with contextlib.redirect_stdout(_sys.stderr):
                cli_main(argv)
            return time.perf_counter() - t0

        run("warm", [])  # weights, compiles, persistent cache
        off = run("off", ["health=false"])
        on = run("on", ["health=true"])
    return {"families": list(families), "n_copies": n_copies,
            "off_s": round(off, 2), "on_s": round(on, 2),
            "overhead_ratio": round(on / off, 3)}


def bench_parity_overhead(families=("resnet", "clip", "s3d"),
                          n_copies: int = 2) -> dict:
    """Wall-clock cost of parity=true (telemetry/parity.py) on the same
    smoke corpus as bench_trace_overhead: the multi-family CLI run,
    warmed untimed, then timed with parity=false and parity=true into
    fresh output dirs. The instrumented paths are the transform-seam
    wrapper (two digests per frame, bounded at 4 per seam/key) plus one
    digest per backbone batch and head key; past the per-key bound every
    tap is a single counter check — the acceptance bar is <= 1.05x like
    the other observability knobs."""
    import contextlib
    import shutil
    import sys as _sys
    import tempfile
    from pathlib import Path

    sample = Path(__file__).parent / "tests" / "assets" / "v_synth_sample.mp4"
    if not sample.exists():
        sample = Path("/root/reference/sample/v_GGSY1Qvo990.mp4")
    if not sample.exists():
        raise FileNotFoundError("no sample video for the parity bench")
    from video_features_tpu.cli import main as cli_main
    base = ["allow_random_weights=true", "on_extraction=save_numpy",
            "extraction_fps=4", "batch_size=32"]
    with tempfile.TemporaryDirectory(prefix="vft_bench_parity_") as td:
        vids = []
        for i in range(n_copies):
            dst = Path(td) / f"sample_parity{i}.mp4"
            shutil.copy(sample, dst)
            vids.append(str(dst))

        def run(out: str, extra) -> float:
            argv = [f"feature_type={','.join(families)}",
                    f"output_path={td}/{out}", f"tmp_path={td}/tmp",
                    "video_paths=[" + ",".join(vids) + "]"] + base + extra
            t0 = time.perf_counter()
            with contextlib.redirect_stdout(_sys.stderr):
                cli_main(argv)
            return time.perf_counter() - t0

        run("warm", [])  # weights, compiles, persistent cache
        off = run("off", ["parity=false"])
        on = run("on", ["parity=true"])
    return {"families": list(families), "n_copies": n_copies,
            "off_s": round(off, 2), "on_s": round(on, 2),
            "overhead_ratio": round(on / off, 3)}


def bench_roofline_overhead(families=("resnet", "clip", "s3d"),
                            n_copies: int = 2) -> dict:
    """Wall-clock cost of roofline=true (telemetry/roofline.py) on the
    same smoke corpus as bench_trace_overhead: the multi-family CLI run,
    warmed untimed (which also seeds the per-device-kind peak cache, so
    the timed run never pays the 2048^3 microbench), then timed with
    roofline=false and roofline=true into fresh output dirs. The
    instrumented paths are one AOT lowering per (runner, batch shape) —
    once, at first dispatch — plus a dict hit per further dispatch and
    the chained stage hook; the acceptance bar is <= 1.05x like the
    other always-on observability knobs."""
    import contextlib
    import shutil
    import sys as _sys
    import tempfile
    from pathlib import Path

    sample = Path(__file__).parent / "tests" / "assets" / "v_synth_sample.mp4"
    if not sample.exists():
        sample = Path("/root/reference/sample/v_GGSY1Qvo990.mp4")
    if not sample.exists():
        raise FileNotFoundError("no sample video for the roofline bench")
    from video_features_tpu.cli import main as cli_main
    base = ["allow_random_weights=true", "on_extraction=save_numpy",
            "extraction_fps=4", "batch_size=32"]
    with tempfile.TemporaryDirectory(prefix="vft_bench_roofline_") as td:
        vids = []
        for i in range(n_copies):
            dst = Path(td) / f"sample_roofline{i}.mp4"
            shutil.copy(sample, dst)
            vids.append(str(dst))

        def run(out: str, extra) -> float:
            argv = [f"feature_type={','.join(families)}",
                    f"output_path={td}/{out}", f"tmp_path={td}/tmp",
                    "video_paths=[" + ",".join(vids) + "]"] + base + extra
            t0 = time.perf_counter()
            with contextlib.redirect_stdout(_sys.stderr):
                cli_main(argv)
            return time.perf_counter() - t0

        # warm pass WITH roofline: weights, compiles, persistent cache,
        # and the device peak cache all hot before the timed A/B
        run("warm", ["roofline=true"])
        off = run("off", ["roofline=false"])
        on = run("on", ["roofline=true"])
    return {"families": list(families), "n_copies": n_copies,
            "off_s": round(off, 2), "on_s": round(on, 2),
            "overhead_ratio": round(on / off, 3)}


def bench_inject_overhead(families=("resnet", "clip", "s3d"),
                          n_copies: int = 2) -> dict:
    """Wall-clock cost of the fault-injection sites (utils/inject.py) on
    the same smoke corpus as bench_trace_overhead: the multi-family CLI
    run, warmed untimed, then timed injection-off and with an ARMED plan
    whose trigger can never fire. Off is the production path (every site
    one global read); armed-but-quiet additionally pays the per-hit
    counting plus the sinks' python atomic path — both must stay inside
    the <= 1.05x budget the other always-on knobs hold."""
    import contextlib
    import shutil
    import sys as _sys
    import tempfile
    from pathlib import Path

    sample = Path(__file__).parent / "tests" / "assets" / "v_synth_sample.mp4"
    if not sample.exists():
        sample = Path("/root/reference/sample/v_GGSY1Qvo990.mp4")
    if not sample.exists():
        raise FileNotFoundError("no sample video for the inject bench")
    from video_features_tpu.cli import main as cli_main
    base = ["allow_random_weights=true", "on_extraction=save_numpy",
            "extraction_fps=4", "batch_size=32"]
    with tempfile.TemporaryDirectory(prefix="vft_bench_inject_") as td:
        vids = []
        for i in range(n_copies):
            dst = Path(td) / f"sample_inject{i}.mp4"
            shutil.copy(sample, dst)
            vids.append(str(dst))

        def run(out: str, extra) -> float:
            argv = [f"feature_type={','.join(families)}",
                    f"output_path={td}/{out}", f"tmp_path={td}/tmp",
                    "video_paths=[" + ",".join(vids) + "]"] + base + extra
            t0 = time.perf_counter()
            with contextlib.redirect_stdout(_sys.stderr):
                cli_main(argv)
            return time.perf_counter() - t0

        run("warm", [])  # weights, compiles, persistent cache
        off = run("off", [])
        on = run("on", ["inject=seed=1;decode.read=eio@n999999999;"
                        "sink.fsync=eio@n999999999"])
    return {"families": list(families), "n_copies": n_copies,
            "off_s": round(off, 2), "on_s": round(on, 2),
            "overhead_ratio": round(on / off, 3)}


def bench_slo_overhead(families=("resnet", "clip", "s3d"),
                       n_copies: int = 2) -> dict:
    """Wall-clock cost of the fleet ops plane (ISSUE 10: request-id
    correlation + serve SLO accounting) on the same smoke corpus as
    bench_trace_overhead. ``off`` is the stock path — every correlated
    emitter added exactly one thread-local read there, which must stay
    free; ``on`` runs telemetry+health under an armed request context
    (telemetry/context.py use_request), i.e. the serve-grade stamping
    path: request ids into span/health records plus the histogram
    observes the SLO split rides on. Budget <= 1.05x, tracked per round
    like the trace/health/inject ratios."""
    import contextlib
    import shutil
    import sys as _sys
    import tempfile
    from pathlib import Path

    sample = Path(__file__).parent / "tests" / "assets" / "v_synth_sample.mp4"
    if not sample.exists():
        sample = Path("/root/reference/sample/v_GGSY1Qvo990.mp4")
    if not sample.exists():
        raise FileNotFoundError("no sample video for the SLO bench")
    from video_features_tpu.cli import main as cli_main
    from video_features_tpu.telemetry import use_request
    base = ["allow_random_weights=true", "on_extraction=save_numpy",
            "extraction_fps=4", "batch_size=32"]
    with tempfile.TemporaryDirectory(prefix="vft_bench_slo_") as td:
        vids = []
        for i in range(n_copies):
            dst = Path(td) / f"sample_slo{i}.mp4"
            shutil.copy(sample, dst)
            vids.append(str(dst))

        def run(out: str, extra, request_id=None) -> float:
            argv = [f"feature_type={','.join(families)}",
                    f"output_path={td}/{out}", f"tmp_path={td}/tmp",
                    "video_paths=[" + ",".join(vids) + "]"] + base + extra
            t0 = time.perf_counter()
            with contextlib.redirect_stdout(_sys.stderr):
                if request_id is None:
                    cli_main(argv)
                else:
                    with use_request(request_id):
                        cli_main(argv)
            return time.perf_counter() - t0

        run("warm", [])  # weights, compiles, persistent cache
        off = run("off", [])
        on = run("on", ["telemetry=true", "health=true",
                        "metrics_interval_s=60"],
                 request_id="bench-request")
    return {"families": list(families), "n_copies": n_copies,
            "off_s": round(off, 2), "on_s": round(on, 2),
            "overhead_ratio": round(on / off, 3)}


def bench_alert_overhead(families=("resnet", "clip", "s3d"),
                         n_copies: int = 2) -> dict:
    """Wall-clock cost of the alerting & flight-recorder plane (ISSUE
    13) on the same smoke corpus as the other observability ratios.
    Both arms run ``telemetry=true`` with a 1s heartbeat so the tick
    machinery itself is in the baseline; ``on`` adds ``history=true
    alerts=true`` — per-tick history sampling + compaction accounting
    AND a full rule-engine evaluation (heartbeat collection, queue
    counts, history windows) per tick, the quiet-fleet steady state.
    No rule fires (nothing to capture), so the ratio isolates the
    always-on cost. Budget <= 1.05x, tracked per round like the
    trace/health/inject/slo ratios."""
    import contextlib
    import shutil
    import sys as _sys
    import tempfile
    from pathlib import Path

    sample = Path(__file__).parent / "tests" / "assets" / "v_synth_sample.mp4"
    if not sample.exists():
        sample = Path("/root/reference/sample/v_GGSY1Qvo990.mp4")
    if not sample.exists():
        raise FileNotFoundError("no sample video for the alert bench")
    from video_features_tpu.cli import main as cli_main
    base = ["allow_random_weights=true", "on_extraction=save_numpy",
            "extraction_fps=4", "batch_size=32", "telemetry=true",
            "metrics_interval_s=1"]
    with tempfile.TemporaryDirectory(prefix="vft_bench_alert_") as td:
        vids = []
        for i in range(n_copies):
            dst = Path(td) / f"sample_alert{i}.mp4"
            shutil.copy(sample, dst)
            vids.append(str(dst))

        def run(out: str, extra) -> float:
            argv = [f"feature_type={','.join(families)}",
                    f"output_path={td}/{out}", f"tmp_path={td}/tmp",
                    "video_paths=[" + ",".join(vids) + "]"] + base + extra
            t0 = time.perf_counter()
            with contextlib.redirect_stdout(_sys.stderr):
                cli_main(argv)
            return time.perf_counter() - t0

        run("warm", [])  # weights, compiles, persistent cache
        off = run("off", [])
        on = run("on", ["history=true", "alerts=true"])
    return {"families": list(families), "n_copies": n_copies,
            "off_s": round(off, 2), "on_s": round(on, 2),
            "overhead_ratio": round(on / off, 3)}


def bench_gc_overhead(families=("resnet", "clip", "s3d"),
                      n_copies: int = 2) -> dict:
    """Wall-clock cost of the storage-accounting plane (gc.py
    GcMonitor) on the same smoke corpus as the other observability
    ratios. Both arms run ``telemetry=true`` with a 1s heartbeat so the
    tick machinery is in the baseline; ``on`` adds ``gc=true`` with a
    quota and ``gc_interval_s=1`` — a full per-plane tree walk plus the
    vft_gc_* gauge publication on (at least) every heartbeat, the
    worst-case accounting cadence (production default is 300s). The
    EVICTION half never runs in-process — that is vft-gc's own process
    — so this ratio isolates exactly what gc=true costs a run. Budget
    <= 1.05x, tracked per round like the trace/inject/slo/alert
    ratios."""
    import contextlib
    import shutil
    import sys as _sys
    import tempfile
    from pathlib import Path

    sample = Path(__file__).parent / "tests" / "assets" / "v_synth_sample.mp4"
    if not sample.exists():
        sample = Path("/root/reference/sample/v_GGSY1Qvo990.mp4")
    if not sample.exists():
        raise FileNotFoundError("no sample video for the gc bench")
    from video_features_tpu.cli import main as cli_main
    base = ["allow_random_weights=true", "on_extraction=save_numpy",
            "extraction_fps=4", "batch_size=32", "telemetry=true",
            "metrics_interval_s=1"]
    with tempfile.TemporaryDirectory(prefix="vft_bench_gc_") as td:
        vids = []
        for i in range(n_copies):
            dst = Path(td) / f"sample_gc{i}.mp4"
            shutil.copy(sample, dst)
            vids.append(str(dst))

        def run(out: str, extra) -> float:
            argv = [f"feature_type={','.join(families)}",
                    f"output_path={td}/{out}", f"tmp_path={td}/tmp",
                    "video_paths=[" + ",".join(vids) + "]"] + base + extra
            t0 = time.perf_counter()
            with contextlib.redirect_stdout(_sys.stderr):
                cli_main(argv)
            return time.perf_counter() - t0

        run("warm", [])  # weights, compiles, persistent cache
        off = run("off", [])
        on = run("on", ["gc=true", "gc_quota_gb=100", "gc_interval_s=1"])
    return {"families": list(families), "n_copies": n_copies,
            "off_s": round(off, 2), "on_s": round(on, 2),
            "overhead_ratio": round(on / off, 3)}


def bench_cache(family: str = "resnet", n_copies: int = 3) -> dict:
    """Repeat-content avoidance ratio (ISSUE 7): the SAME corpus run
    twice with ``cache=true`` into a fresh content-addressed store
    (cache.py) — pass 1 pays decode+device (every video a miss), pass 2
    must be served from the store. Compiles are warmed untimed first so
    the ratio measures the cache, not XLA. The warm pass runs with
    ``trace=true`` and ships its per-stage breakdown: near-zero decode
    and device ms is the acceptance shape (work NOT done, not merely
    done faster). Outputs are verified bit-identical between passes —
    a speedup that changed the features would be a correctness bug
    wearing a bench medal. Run standalone: ``python bench.py
    bench_cache``."""
    import contextlib
    import shutil
    import sys as _sys
    import tempfile
    from pathlib import Path

    sample = Path(__file__).parent / "tests" / "assets" / "v_synth_sample.mp4"
    if not sample.exists():
        sample = Path("/root/reference/sample/v_GGSY1Qvo990.mp4")
    if not sample.exists():
        raise FileNotFoundError("no sample video for the cache bench")
    from video_features_tpu.cli import main as cli_main
    with tempfile.TemporaryDirectory(prefix="vft_bench_cache_") as td:
        vids = []
        for i in range(n_copies):
            dst = Path(td) / f"sample_cache{i}.mp4"
            shutil.copy(sample, dst)
            vids.append(str(dst))
        base = [f"feature_type={family}", "allow_random_weights=true",
                "on_extraction=save_numpy", "extraction_fps=4",
                "batch_size=32", "cache=true", f"cache_dir={td}/store",
                f"tmp_path={td}/tmp",
                "video_paths=[" + ",".join(vids) + "]"]

        def run(out: str, extra) -> float:
            t0 = time.perf_counter()
            with contextlib.redirect_stdout(_sys.stderr):
                cli_main(base + [f"output_path={td}/{out}"] + extra)
            return time.perf_counter() - t0

        # compile warmup OUTSIDE the store (cache=false, 1 video): pass 1
        # must measure a true miss pass, not the one-time XLA tax
        run("warm", ["cache=false",
                     f"video_paths=[{vids[0]}]"])
        cold = run("cold", [])
        warm = run("hot", ["trace=true"])
        outs_cold = sorted(p.relative_to(Path(td, "cold"))
                           for p in Path(td, "cold").rglob("*.npy"))
        outs_warm = sorted(p.relative_to(Path(td, "hot"))
                           for p in Path(td, "hot").rglob("*.npy"))
        if outs_cold != outs_warm or len(outs_cold) < n_copies:
            raise RuntimeError(
                f"cache bench: pass outputs diverged or incomplete "
                f"({len(outs_cold)} vs {len(outs_warm)} artifacts)")
        for rel in outs_cold:
            if Path(td, "cold", rel).read_bytes() != \
                    Path(td, "hot", rel).read_bytes():
                raise RuntimeError(
                    f"cache bench: {rel} not bit-identical across passes "
                    "— a hit served different features")
        stages = None
        try:
            sys.path.insert(0, str(Path(__file__).parent / "scripts"))
            import trace_report
            traces = sorted(Path(td, "hot").rglob(
                trace_report.TRACE_FILENAME))
            if traces:
                stages = trace_report.stage_summary(str(traces[0].parent))
        except BaseException as e:  # breakdown is telemetry, not the metric
            print(f"WARNING: cache-bench stage breakdown failed: "
                  f"{type(e).__name__}: {e}", file=_sys.stderr)
    result = {"family": family, "n_copies": n_copies,
              "cold_s": round(cold, 2), "warm_s": round(warm, 3),
              "speedup": round(cold / warm, 1),
              "artifacts_bit_identical": True}
    if stages:
        result["warm_stages"] = stages
    return result


def bench_fleet(n_small: int = 6, skew: float = 4.0, unit_s: float = 0.4,
                n_hosts: int = 2, n_real: int = 3) -> dict:
    """Fleet scheduling makespan: static hash-sharding vs the
    work-stealing queue (parallel/queue.py) under injected 4x skew —
    one oversized video in a corpus whose hash shard assignment lands it
    on the already-fuller host (the failure mode hash sharding cannot
    see: it knows stems, not durations).

    Two halves:

    1. **Simulated makespan A/B** (the ratio row): work items are
       sleeps, so N workers overlap perfectly even on a 1-core bench
       host and the measured delta is pure *scheduling* — real
       extraction under N threads on one core is total-work-bound either
       way, which would mask exactly the effect this row tracks. Static
       runs each host's md5 shard sequentially; queue runs the real
       WorkQueue claim/steal discipline over a shared root. The
       oversized item is named to sort first (claim order is name
       order), the documented operator move for known-long videos.
    2. **Real exactly-once / bit-identity check**: ``n_real`` sample
       copies drained by 2 real ``fleet=queue`` CLI worker processes
       sharing an output dir, asserted against a ``fleet=static``
       reference run — identical artifact bytes, identical PR-5 health
       content signatures, one done marker per video, zero reclaims.
       A makespan win that double-extracted or drifted a feature would
       fail here, not ship.
    """
    import shutil
    import subprocess
    import sys as _sys
    import tempfile
    import textwrap
    import threading
    from pathlib import Path

    from video_features_tpu.parallel.mesh import local_shard_of_list
    from video_features_tpu.parallel.queue import WorkQueue
    from video_features_tpu.telemetry.jsonl import write_json_atomic

    # ---- half 1: simulated makespan A/B --------------------------------
    # deterministic salt search: hash sharding WILL deal hands this bad
    # (any corpus has some worst host); the bench pins one such hand so
    # the ratio is reproducible round over round
    big, smalls = None, None
    for salt in range(5000):
        cand_big = f"a-long-{salt}.mp4"  # 'a-' sorts first == claimed first
        cand_smalls = [f"s{i:02d}-{salt}.mp4" for i in range(n_small)]
        shard0 = set(local_shard_of_list([cand_big] + cand_smalls,
                                         host_id=0, num_hosts=n_hosts))
        owner = shard0 if cand_big in shard0 else \
            set([cand_big] + cand_smalls) - shard0
        if len(owner) == n_small:  # big + all-but-one small on one host
            big, smalls = cand_big, cand_smalls
            break
    assert big is not None, "no skewed salt found in 5000 tries"
    items = [big] + smalls
    dur = {v: (skew * unit_s if v == big else unit_s) for v in items}

    def _static_makespan() -> float:
        shards = [local_shard_of_list(items, host_id=h, num_hosts=n_hosts)
                  for h in range(n_hosts)]

        def host(shard):
            for v in shard:
                time.sleep(dur[v])
        threads = [threading.Thread(target=host, args=(s,)) for s in shards]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0

    def _queue_makespan() -> float:
        with tempfile.TemporaryDirectory(prefix="vft_bench_fleet_") as td:
            queues = []
            for h in range(n_hosts):
                hid = f"simhost{h}"
                # live heartbeats: without one, siblings would judge the
                # owner dead and steal unexpired leases (the real CLI's
                # recorder writes this before any claim)
                write_json_atomic(
                    os.path.join(td, f"_heartbeat_{hid}.json"),
                    {"host_id": hid, "time": time.time(),
                     "interval_s": 60.0, "final": False})
                queues.append(WorkQueue(td, host_id=hid, lease_s=60.0))
            for q in queues:
                q.seed(items)

            def host(q):
                q.drain(lambda v: (time.sleep(dur[v]), "done")[1],
                        workers=1, poll_s=0.02)
            threads = [threading.Thread(target=host, args=(q,))
                       for q in queues]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            done = sum(1 for n in os.listdir(
                os.path.join(td, "_queue", "done")) if n.endswith(".json"))
            assert done == len(items), \
                f"queue drained {done}/{len(items)} items"
        return wall

    static_s = _static_makespan()
    queue_s = _queue_makespan()

    # ---- half 2: real workers, exactly-once + bit-identical -------------
    sample = Path(__file__).parent / "tests" / "assets" / "v_synth_sample.mp4"
    if not sample.exists():
        sample = Path("/root/reference/sample/v_GGSY1Qvo990.mp4")
    if not sample.exists():
        raise FileNotFoundError("no sample video for the fleet bench")
    worker_src = textwrap.dedent("""
        import sys
        sys.path.insert(0, {repo!r})
        import jax
        jax.config.update("jax_platforms", "cpu")
        from video_features_tpu.cli import main
        main([
            "feature_type=resnet", "model_name=resnet18", "device=cpu",
            "allow_random_weights=true", "on_extraction=save_numpy",
            "extraction_total=6", "batch_size=8", "video_workers=1",
            "telemetry=true", "health=true", "metrics_interval_s=0.5",
            {fleet_args}
            "output_path={out}", "tmp_path={tmp}",
            "file_with_video_paths={listfile}",
        ])
    """)

    def _spawn(td, out, fleet_args, tag):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        log = open(Path(td) / f"{tag}.log", "w")
        proc = subprocess.Popen(
            [_sys.executable, "-c", worker_src.format(
                repo=str(Path(__file__).parent), fleet_args=fleet_args,
                out=out, tmp=f"{td}/tmp_{tag}",
                listfile=f"{td}/videos.txt")],
            stdout=log, stderr=subprocess.STDOUT, env=env)
        return proc, log

    with tempfile.TemporaryDirectory(prefix="vft_bench_fleet_real_") as td:
        vids = []
        for i in range(n_real):
            dst = Path(td) / f"fleet{i}.mp4"
            shutil.copy(sample, dst)
            vids.append(str(dst))
        (Path(td) / "videos.txt").write_text("\n".join(vids) + "\n")
        ref, ref_log = _spawn(td, f"{td}/ref", "", "ref")
        assert ref.wait(timeout=560) == 0, \
            (Path(td) / "ref.log").read_text()[-2000:]
        ref_log.close()
        procs = [_spawn(td, f"{td}/q",
                        '"fleet=queue", "fleet_lease_s=10",', f"w{i}")
                 for i in range(2)]
        for proc, log in procs:
            rc = proc.wait(timeout=560)
            log.close()
            assert rc == 0, (Path(td) / "w0.log").read_text()[-2000:]

        ref_npy = sorted(p.relative_to(f"{td}/ref")
                         for p in Path(td, "ref").rglob("*.npy"))
        q_npy = sorted(p.relative_to(f"{td}/q")
                       for p in Path(td, "q").rglob("*.npy"))
        assert ref_npy == q_npy, \
            f"artifact sets diverged: static={len(ref_npy)} queue={len(q_npy)}"
        assert sum(1 for rel in q_npy
                   if str(rel).endswith("_resnet.npy")) == n_real
        for rel in ref_npy:
            assert Path(td, "ref", rel).read_bytes() == \
                Path(td, "q", rel).read_bytes(), \
                f"{rel}: queue output not bit-identical to static run"
        done_dir = Path(td) / "q" / "resnet" / "resnet18" / "_queue" / "done"
        done = sorted(done_dir.glob("*.json"))
        assert len(done) == n_real, \
            f"{len(done)} done markers for {n_real} videos"
        for p in done:
            rec = json.loads(p.read_text())
            assert rec["status"] in ("done", "skipped") and \
                rec["reclaims"] == 0, rec
        # PR-5 health digests: identical content signatures per
        # (video, family, key) across the two scheduling modes
        sys.path.insert(0, str(Path(__file__).parent / "scripts"))
        import compare_runs
        ha = compare_runs.load_health(f"{td}/ref")
        hb = compare_runs.load_health(f"{td}/q")
        assert set(ha) == set(hb) and len(ha) >= n_real
        for k in ha:
            assert ha[k].get("sig") == hb[k].get("sig"), \
                f"health signature drift on {k}"

    return {"n_hosts": n_hosts, "skew": skew, "unit_s": unit_s,
            "corpus": f"{n_small} smalls + 1 oversized ({skew}x)",
            "static_makespan_s": round(static_s, 3),
            "queue_makespan_s": round(queue_s, 3),
            "makespan_ratio": round(static_s / queue_s, 2),
            "real_videos": n_real, "bit_identical": True,
            "extracted_exactly_once": True, "health_digests_equal": True}


#: the coldstart/churn benches' work unit: RAFT at a small side keeps
#: the compile:inference ratio high (a 20-iteration GRU scan compiles
#: for seconds; three frames of flow infer in ~1), so the warm-start
#: delta is the signal, not the noise
_COLDSTART_ARGS = ("feature_type=raft", "device=cpu",
                   "allow_random_weights=true", "on_extraction=save_numpy",
                   "extraction_total=3", "batch_size=1", "side_size=96",
                   "telemetry=true")


def _coldstart_worker_src() -> str:
    import textwrap
    return textwrap.dedent("""
        import json, sys, time, contextlib
        sys.path.insert(0, {repo!r})
        import jax
        jax.config.update("jax_platforms", "cpu")
        from video_features_tpu.cli import main
        t0 = time.perf_counter()
        with contextlib.redirect_stdout(sys.stderr):
            main(json.loads(sys.argv[1]))
        print("VFT_BENCH_SECONDS", round(time.perf_counter() - t0, 3))
    """)


def _read_manifest_compile_cache(out_dir) -> dict:
    from pathlib import Path
    for p in sorted(Path(out_dir).rglob("_run.json")):
        doc = json.loads(p.read_text())
        cc = doc.get("compile_cache")
        if cc is not None:
            return cc
    return {}


def bench_coldstart() -> dict:
    """Join latency as a number (ISSUE 11): the first-inference latency
    of a COLD process (empty fleet compile store — every program is an
    XLA compile) vs a WARM one (same triple, store sealed by the cold
    run — every program is a verified deserialize). Two real fresh
    processes, because compile warmth is precisely a cross-process
    property; import time is excluded on both sides (the worker times
    ``cli_main`` only). Features must be bit-identical across the two
    passes — an executable served from the store that computed different
    bytes would be the SIGILL-adjacent failure mode the environment
    fingerprint exists to prevent. Acceptance: warm >= 2x faster, warm
    hits > 0. Run standalone: ``python bench.py bench_coldstart``."""
    import shutil
    import subprocess
    import sys as _sys
    import tempfile
    from pathlib import Path

    sample = Path(__file__).parent / "tests" / "assets" / "v_synth_sample.mp4"
    if not sample.exists():
        sample = Path("/root/reference/sample/v_GGSY1Qvo990.mp4")
    if not sample.exists():
        raise FileNotFoundError("no sample video for the coldstart bench")

    def run(td: str, out: str, extra=()) -> float:
        argv = list(_COLDSTART_ARGS) + [
            "compile_cache=true", f"compile_cache_dir={td}/cc_store",
            f"output_path={td}/{out}", f"tmp_path={td}/tmp_{out}",
            f"video_paths=[{td}/cold.mp4]"] + list(extra)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [_sys.executable, "-c", _coldstart_worker_src().format(
                repo=str(Path(__file__).parent)), json.dumps(argv)],
            capture_output=True, text=True, env=env)
        if proc.returncode != 0:
            raise RuntimeError(f"coldstart worker failed: "
                               f"{(proc.stderr or '')[-2000:]}")
        for line in reversed(proc.stdout.splitlines()):
            if line.startswith("VFT_BENCH_SECONDS"):
                return float(line.split()[1])
        raise RuntimeError("coldstart worker printed no timing")

    with tempfile.TemporaryDirectory(prefix="vft_bench_coldstart_") as td:
        shutil.copy(sample, Path(td) / "cold.mp4")
        cold_s = run(td, "p1")
        cold_cc = _read_manifest_compile_cache(Path(td) / "p1")
        warm_s = run(td, "p2")
        warm_cc = _read_manifest_compile_cache(Path(td) / "p2")
        p1 = sorted(p.relative_to(Path(td) / "p1")
                    for p in (Path(td) / "p1").rglob("*.npy"))
        p2 = sorted(p.relative_to(Path(td) / "p2")
                    for p in (Path(td) / "p2").rglob("*.npy"))
        if p1 != p2 or not p1:
            raise RuntimeError(f"coldstart passes diverged: {len(p1)} vs "
                               f"{len(p2)} artifacts")
        for rel in p1:
            if (Path(td) / "p1" / rel).read_bytes() != \
                    (Path(td) / "p2" / rel).read_bytes():
                raise RuntimeError(
                    f"{rel}: warm-process features differ from cold — a "
                    "deserialized executable computed different bytes")
        if not int(warm_cc.get("hits", 0)):
            raise RuntimeError(f"warm process reported no compile-cache "
                               f"hits: {warm_cc}")
    return {"family": "raft", "cold_s": round(cold_s, 2),
            "warm_s": round(warm_s, 2),
            "speedup": round(cold_s / warm_s, 2),
            "cold_compiles": int(cold_cc.get("misses", 0)),
            "warm_hits": int(warm_cc.get("hits", 0)),
            "warm_misses": int(warm_cc.get("misses", 0)),
            "bit_identical": True}


def _churn_worker_src() -> str:
    import textwrap
    return textwrap.dedent("""
        import json, sys
        sys.path.insert(0, {repo!r})
        import jax
        jax.config.update("jax_platforms", "cpu")
        from video_features_tpu.cli import main
        main(json.loads(sys.argv[1]))
    """)


def bench_fleet_churn(rates=(0.0, 0.25, 0.5), n_videos: int = 8,
                      n_workers: int = 2) -> dict:
    """Preemptible churn as a recorded scenario (ISSUE 11 / ROADMAP 3b):
    a real ``fleet=queue`` fleet drains the same corpus under
    ``inject worker.kill@p`` (PR 9's deterministic SIGKILL site) at
    several churn rates; killed workers are respawned — the spot-market
    shape — and the *makespan degradation curve* is the published
    number, next to bench_fleet's scheduling ratio. The whole curve runs
    with warm-start ON (the compile store pre-sealed, so every respawn
    re-joins without compiling); one extra run at the middle rate with
    ``compile_cache=false`` measures the rejoin penalty the store
    removes. Every run must end in vft-audit PASS — a churn number over
    a corrupted output dir would be worthless. Run standalone:
    ``python bench.py bench_fleet_churn``."""
    import contextlib
    import io
    import shutil
    import subprocess
    import sys as _sys
    import tempfile
    from pathlib import Path

    sample = Path(__file__).parent / "tests" / "assets" / "v_synth_sample.mp4"
    if not sample.exists():
        sample = Path("/root/reference/sample/v_GGSY1Qvo990.mp4")
    if not sample.exists():
        raise FileNotFoundError("no sample video for the churn bench")
    from video_features_tpu.audit import main as audit_main
    worker_src = _churn_worker_src().format(repo=str(Path(__file__).parent))
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def spawn(td, out, listfile, tag, inject_plan, warm: bool):
        argv = list(_COLDSTART_ARGS) + [
            "fleet=queue", "fleet_lease_s=6", "fleet_max_reclaims=6",
            "metrics_interval_s=1", "health=true",
            "compile_cache=true" if warm else "compile_cache=false",
            f"compile_cache_dir={td}/cc_store",
            f"output_path={out}", f"tmp_path={td}/tmp_{tag}",
            f"file_with_video_paths={listfile}"]
        if inject_plan:
            argv.append(f"inject={inject_plan}")
        log = open(Path(td) / f"{tag}.log", "w")
        proc = subprocess.Popen(
            [_sys.executable, "-c", worker_src, json.dumps(argv)],
            stdout=log, stderr=subprocess.STDOUT, env=env)
        return proc, log

    def drain_counts(out: Path) -> dict:
        done = quarantined = pending = claimed = 0
        for q in out.rglob("_queue"):
            done += sum(1 for n in (q / "done").glob("*.json"))
            quarantined += sum(1 for n in (q / "quarantined").glob("*.json"))
            pending += sum(1 for n in (q / "pending").glob("*.json"))
            for h in (q / "claimed").glob("*"):
                claimed += sum(1 for n in h.glob("*.json"))
        return {"done": done, "quarantined": quarantined,
                "pending": pending, "claimed": claimed}

    def run_rate(td, listfile, rate: float, tag: str, warm: bool,
                 deadline_s: float = 420.0) -> dict:
        out = Path(td) / f"out_{tag}"
        procs = []
        spawns = 0
        kills = 0
        t0 = time.perf_counter()
        for i in range(n_workers):
            plan = (f"seed={spawns * 13 + 7};worker.kill=kill@p{rate}"
                    if rate > 0 else None)
            procs.append(spawn(td, str(out), listfile,
                               f"{tag}_w{spawns}", plan, warm))
            spawns += 1
        drained_at = None
        while True:
            c = drain_counts(out)
            settled = c["done"] + c["quarantined"]
            if settled >= n_videos and not c["pending"] and \
                    not c["claimed"]:
                drained_at = time.perf_counter() - t0
                break
            if time.perf_counter() - t0 > deadline_s:
                for p, log in procs:
                    with contextlib.suppress(OSError):
                        p.kill()
                raise RuntimeError(
                    f"churn rate {rate}: not drained in {deadline_s}s "
                    f"(counts {c})")
            still = []
            for p, log in procs:
                rc = p.poll()
                if rc is None:
                    still.append((p, log))
                    continue
                log.close()
                if rc in (0, 143):
                    continue  # drained (or drained on SIGTERM) — done
                # SIGKILLed by its own injection: the preempted host.
                # Respawn = a replacement host joining mid-run.
                kills += 1
                if spawns < n_workers + 12:
                    plan = (f"seed={spawns * 13 + 7};"
                            f"worker.kill=kill@p{rate}"
                            if rate > 0 else None)
                    still.append(spawn(td, str(out), listfile,
                                       f"{tag}_w{spawns}", plan, warm))
                    spawns += 1
            procs = still
            if not procs and spawns >= n_workers + 12:
                raise RuntimeError(f"churn rate {rate}: respawn cap hit "
                                   "with queue undrained")
            time.sleep(0.4)
        for p, log in procs:
            # survivors see all_done and exit on their own
            try:
                p.wait(timeout=120)
            finally:
                log.close()
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            audit_rc = audit_main([str(out)])
        if audit_rc != 0:
            raise RuntimeError(f"churn rate {rate}: vft-audit FAIL:\n"
                               + buf.getvalue()[-2000:])
        c = drain_counts(out)
        return {"rate": rate, "makespan_s": round(drained_at, 2),
                "kills": kills, "workers_spawned": spawns,
                "done": c["done"], "quarantined": c["quarantined"],
                "audit": "PASS"}

    with tempfile.TemporaryDirectory(prefix="vft_bench_churn_") as td:
        vids = []
        for i in range(n_videos):
            dst = Path(td) / f"churn{i}.mp4"
            shutil.copy(sample, dst)
            vids.append(str(dst))
        listfile = str(Path(td) / "videos.txt")
        Path(listfile).write_text("\n".join(vids) + "\n")
        # pre-seal the store so EVERY warm run (first workers and
        # respawns alike) attaches warm — the elastic-join contract
        prewarm = spawn(td, str(Path(td) / "out_prewarm"), listfile,
                        "prewarm", None, warm=True)
        rc = prewarm[0].wait(timeout=420)
        prewarm[1].close()
        if rc != 0:
            raise RuntimeError(
                "churn prewarm failed: "
                + (Path(td) / "prewarm.log").read_text()[-2000:])
        curve = [run_rate(td, listfile, r, f"r{int(r * 100)}", warm=True)
                 for r in rates]
        mid = rates[len(rates) // 2]
        cold = run_rate(td, listfile, mid, "cold", warm=False)
    base = curve[0]["makespan_s"]
    warm_mid = next(p for p in curve if p["rate"] == mid)
    return {
        "n_videos": n_videos, "n_workers": n_workers,
        "curve": curve,
        "degradation_at_max": round(curve[-1]["makespan_s"] / base, 2),
        "warm_vs_cold_at_mid": {
            "rate": mid, "warm_s": warm_mid["makespan_s"],
            "cold_s": cold["makespan_s"], "cold_kills": cold["kills"],
            "rejoin_penalty_removed_s": round(
                cold["makespan_s"] - warm_mid["makespan_s"], 2)},
        "audit": "PASS",
    }


def bench_fleet_sustained(n_videos: int = 6, n_workers: int = 2,
                          families: str = "resnet,clip") -> dict:
    """The ROADMAP-5 tail: BENCH's sustained row measures ONE container
    CPU; the system we built is N queue workers sharing one decode pass
    per video over a warm compile store. This bench runs that recorded
    configuration for real — ``n_workers`` ``fleet=queue`` CLI processes
    draining ``n_videos`` DISTINCT synthetic clips (distinct, so the
    feature cache's content dedup cannot stand in for extraction) with
    multi-family shared decode — and reports the fleet extraction rate
    off the workers' own drain-loop walls (imports and warm attach
    excluded). On this 1-core container the two workers time-slice one
    CPU, so the honest expectation is parity with one host, not 2x: the
    row records the SYSTEM's number so multi-core/TPU rounds measure
    scaling against it. Run standalone: ``python bench.py
    bench_fleet_sustained``."""
    import re
    import subprocess
    import sys as _sys
    import tempfile
    from pathlib import Path

    from video_features_tpu.compile_cache import _synth_clip
    worker_src = _churn_worker_src().format(repo=str(Path(__file__).parent))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    fams = families.split(",")

    def spawn(td, out, listfile, tag):
        argv = [f"feature_type={families}", "device=cpu",
                "allow_random_weights=true", "on_extraction=save_numpy",
                "extraction_fps=4", "batch_size=8", "telemetry=true",
                "metrics_interval_s=1", "fleet=queue", "fleet_lease_s=15",
                "compile_cache=true", f"compile_cache_dir={td}/cc_store",
                f"output_path={out}", f"tmp_path={td}/tmp_{tag}",
                f"file_with_video_paths={listfile}"]
        log = open(Path(td) / f"{tag}.log", "w")
        proc = subprocess.Popen(
            [_sys.executable, "-c", worker_src, json.dumps(argv)],
            stdout=log, stderr=subprocess.STDOUT, env=env)
        return proc, log

    with tempfile.TemporaryDirectory(prefix="vft_bench_fsus_") as td:
        vids = []
        for i in range(n_videos):
            # distinct content per clip: phase-shifted gradients, so no
            # two videos share a content hash
            path = str(Path(td) / f"sus{i}.mp4")
            _synth_clip(path, frames=48 + 2 * i)
            vids.append(path)
        listfile = str(Path(td) / "videos.txt")
        Path(listfile).write_text("\n".join(vids) + "\n")
        # warm pass: seals the combined multi-family compile entry
        pre = spawn(td, str(Path(td) / "out_pre"),
                    _write_list(td, vids[:1]), "prewarm")
        rc = pre[0].wait(timeout=600)
        pre[1].close()
        if rc != 0:
            raise RuntimeError("fleet-sustained prewarm failed: "
                               + (Path(td) / "prewarm.log")
                               .read_text()[-2000:])
        procs = [spawn(td, str(Path(td) / "out"), listfile, f"w{i}")
                 for i in range(n_workers)]
        for p, log in procs:
            rc = p.wait(timeout=900)
            log.close()
            if rc != 0:
                raise RuntimeError(
                    "fleet-sustained worker failed: "
                    + (Path(td) / "w0.log").read_text()[-2000:])
        # each worker's drain wall from its own summary line ("V videos x
        # F families in S s"); the fleet makespan is the slowest worker
        walls = []
        for i in range(n_workers):
            text = (Path(td) / f"w{i}.log").read_text()
            m = re.search(r"videos x \d+ families in ([0-9.]+)s", text)
            if m:
                walls.append(float(m.group(1)))
        if not walls:
            raise RuntimeError("no worker drain walls parsed")
        makespan = max(walls)
        done = sum(1 for q in (Path(td) / "out").rglob("_queue")
                   for _ in (q / "done").glob("*.json"))
        if done != n_videos:
            raise RuntimeError(f"{done} done markers for {n_videos} videos")
    extractions = n_videos * len(fams)
    return {"families": fams, "n_videos": n_videos, "n_workers": n_workers,
            "fleet_makespan_s": round(makespan, 2),
            "videos_per_s": round(n_videos / makespan, 3),
            "extractions_per_s": round(extractions / makespan, 3),
            "compile_warm": True, "shared_decode": True}


def _write_list(td, vids) -> str:
    from pathlib import Path
    p = Path(td) / "prewarm.txt"
    p.write_text("\n".join(vids) + "\n")
    return str(p)


def bench_scenario(scenario: str = "burst_shed") -> dict:
    """One checked-in traffic drill (scenarios/*.yml) end to end on a
    virtual clock: seeded loadgen traffic through a real GatewayServer
    over HTTP into a real ServeLoop whose video step is stubbed (the
    drill measures the ADMISSION/SPOOL/JOIN machinery, not the model),
    finishing with the journal join, the vft-audit gate and the
    _scenario.json verdict. The recorded wall seconds are the cost of
    the whole observatory round trip for a fixed offered schedule —
    tracked per round under the bench-history gate so a regression in
    the gateway release loop, the spool protocol or the report join
    shows up as drill seconds, not as an anecdote."""
    import tempfile
    import threading
    from pathlib import Path

    from video_features_tpu import serve
    from video_features_tpu.config import load_config, sanity_check
    from video_features_tpu.gateway import GatewayServer
    from video_features_tpu.loadgen import (DrillRunner, load_scenario,
                                            synthesize_corpus,
                                            write_tenant_table)
    spec = load_scenario(str(Path(__file__).parent / "scenarios" /
                             f"{scenario}.yml"))
    with tempfile.TemporaryDirectory(prefix="vft_bench_scn_") as td:
        td = Path(td)
        spool = td / "spool"
        write_tenant_table([spec], str(td / "tenants.yml"),
                           spec["speedup"] or 1.0)
        cfg = load_config("resnet", {
            "model_name": "resnet18", "device": "cpu",
            "allow_random_weights": True, "on_extraction": "save_numpy",
            "extraction_total": 6, "batch_size": 8, "cache": False,
            "spool_dir": str(spool), "serve_poll_interval_s": 0.02,
            "metrics_interval_s": 1, "serve_slo_s": 120.0,
            "output_path": str(td / "out"), "tmp_path": str(td / "tmp")})
        sanity_check(cfg, require_videos=False)
        loop = serve.ServeLoop(cfg, out_root=str(td / "out"))
        # stub the video step: a small fixed service time keeps queueing
        # dynamics real while removing decode/model noise from the row.
        # Sized for the virtual clock: 5ms wall x speedup 40 = 0.2
        # virtual seconds per video, i.e. an offered load well under
        # capacity — attainment failures then mean the MACHINERY (edge
        # queue, release loop, spool) ate the budget, not the stub
        loop._run_one_video = lambda v: time.sleep(0.005) or {"resnet":
                                                              "done"}
        t = threading.Thread(target=loop.run, daemon=True)
        t.start()
        gw = GatewayServer({"spool_dir": str(spool),
                            "gateway_tenants": str(td / "tenants.yml"),
                            "gateway_poll_interval_s": 0.05,
                            "metrics_interval_s": 1}).start()
        try:
            corpus = synthesize_corpus(str(td / "corpus"), [spec])
            runner = DrillRunner(
                [spec], str(spool), f"http://127.0.0.1:{gw.port}",
                corpus=corpus, audit_root=str(td),
                drain_timeout_s=120.0)
            t0 = time.perf_counter()
            report = runner.run()
            wall = time.perf_counter() - t0
        finally:
            gw.stop()
            loop.stop()
            t.join(timeout=60)
    atts = {name: tb.get("attainment_pct")
            for name, tb in report["tenants"].items()}
    return {"scenario": spec["scenario"], "seed": spec["seed"],
            "wall_s": round(wall, 2),
            "virtual_s": spec["duration_s"],
            "speedup": report["speedup"],
            "offered": report["offered"],
            "admitted": report["admitted"],
            "completed": report["completed"],
            "rejected": report["rejected"],
            "attainment_pct": atts,
            "audit_pass": report["audit"]["pass"],
            "verdict": report["verdict"]}


def bench_i3d_torch(stack: int = I3D_STACK) -> float:
    """The full reference-shaped stack unit in torch on this host's CPU:
    RAFT flow on the frame pairs PLUS both I3D tower forwards (all classes
    imported read-only from /root/reference). Same best-of-TRIALS /
    adaptive >= MIN_TRIAL_SECONDS rigor as bench_torch_reference, applied
    to every term. Absent the reference source, return nan (no baseline)."""
    import importlib.util
    import sys
    from pathlib import Path
    import torch

    ref_root = Path("/root/reference")
    ref_raft = ref_root / "models/raft/raft_src/raft.py"
    ref_i3d = ref_root / "models/i3d/i3d_src/i3d_net.py"
    if not (ref_raft.exists() and ref_i3d.exists()):
        return float("nan")
    # reference raft.py imports via the 'models.raft.raft_src' package path,
    # so the reference ROOT goes on sys.path (same as tests/test_raft.py)
    if str(ref_root) not in sys.path:
        sys.path.insert(0, str(ref_root))

    def _load(name, path):
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    raft = _load("ref_raft", ref_raft).RAFT().eval()  # no args (raft.py:54)
    i3d_net = _load("ref_i3d", ref_i3d)
    towers = {s: i3d_net.I3D(num_classes=400, modality=s).eval()
              for s in ("rgb", "flow")}
    timed = _torch_seconds_per_call

    pairs = 4  # timed pair-batch; flow cost scales linearly to the stack
    x = torch.randint(0, 255, (pairs, 3, I3D_SIDE, I3D_SIDE),
                      dtype=torch.float32)
    with torch.no_grad():
        raft(x[:1], x[:1], iters=2)  # warmup
    t_flow = timed(lambda: raft(x, x, iters=20,
                                test_mode=True)) * (stack / pairs)
    rgb_in = torch.randn(1, 3, stack, I3D_SIDE, I3D_SIDE)
    flow_in = torch.randn(1, 2, stack, I3D_SIDE, I3D_SIDE)
    t_rgb = timed(lambda: towers["rgb"](rgb_in))
    t_flow_tower = timed(lambda: towers["flow"](flow_in))
    return 1.0 / (t_flow + t_rgb + t_flow_tower)


# ---- per-family device-throughput rows (round-4 coverage) ----------------
#
# One row per remaining family, same methodology as the headliners:
# bf16 params+activations (the production precision=bfloat16 mode),
# device-staged inputs, D2H-fenced best-of-trials, torch-CPU-1core ratio on
# the identical work unit. Batch sizes are the extractors' production
# defaults where those exist (clip_batch_size, batch_size in configs/).

def _ref_path(rel: str):
    from pathlib import Path
    p = Path("/root/reference") / rel
    return p if p.exists() else None


def _tests_on_path() -> None:
    """Make tests/torch_oracles.py importable (the reference image lacks
    torchvision; the oracles are the test-only torch re-implementations)."""
    from pathlib import Path
    p = str(Path(__file__).resolve().parent / "tests")
    if p not in sys.path:
        sys.path.insert(0, p)


def _load_ref_module(name: str, rel: str):
    import importlib.util
    path = _ref_path(rel)
    if path is None:
        return None
    if "/root/reference" not in sys.path:
        sys.path.insert(0, "/root/reference")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def bench_resnet50(batch: int = 128, iters: int = 20):
    """(frames/sec on device, seconds/frame in torch-cpu or None)."""
    import jax
    import jax.numpy as jnp
    from video_features_tpu.extractors.resnet import _device_forward
    from video_features_tpu.models import resnet as resnet_m
    from video_features_tpu.parallel.mesh import cast_floating

    model = resnet_m.ResNet("resnet50")
    params = cast_floating(resnet_m.init_params("resnet50")["backbone"],
                           jnp.bfloat16)
    step = jax.jit(lambda p, x: _device_forward(model, jnp.bfloat16, p, x))
    rng = np.random.default_rng(0)
    data = [jax.device_put(rng.integers(0, 255, size=(batch, 224, 224, 3),
                                        dtype=np.uint8)) for _ in range(2)]
    _record_cost("resnet50", step, (params, data[0]))
    ours = _device_rate(step, [(params, d) for d in data], batch, iters)

    def torch_baseline():
        import torch
        _tests_on_path()
        from torch_oracles import TorchResNet
        m = TorchResNet(variant="resnet50").eval()
        x = torch.randn(1, 3, 224, 224)
        m(x)
        return _torch_seconds_per_call(lambda: m(x))
    return ours, torch_baseline


def bench_clip_vit_b32(batch: int = 128, iters: int = 20):
    """(frames/sec through the ViT-B/32 visual tower, torch secs or None)."""
    import jax
    import jax.numpy as jnp
    from video_features_tpu.extractors.clip import _encode_image
    from video_features_tpu.models import clip as clip_m
    from video_features_tpu.parallel.mesh import cast_floating

    model = clip_m.CLIP(clip_m.CONFIGS["ViT-B/32"])
    params = cast_floating(clip_m.init_params("ViT-B/32"), jnp.bfloat16)
    step = jax.jit(lambda p, x: _encode_image(model, jnp.bfloat16, p, x))
    rng = np.random.default_rng(0)
    data = [jax.device_put(rng.integers(0, 255, size=(batch, 224, 224, 3),
                                        dtype=np.uint8)) for _ in range(2)]
    _record_cost("clip", step, (params, data[0]))
    ours = _device_rate(step, [(params, d) for d in data], batch, iters)

    def torch_baseline():
        import torch
        mod = _load_ref_module("ref_clip_model", "models/clip/clip_src/model.py")
        if mod is None:
            return None
        m = mod.CLIP(embed_dim=512, image_resolution=224, vision_layers=12,
                     vision_width=768, vision_patch_size=32,
                     context_length=77, vocab_size=49408,
                     transformer_width=512, transformer_heads=8,
                     transformer_layers=12).eval().float()
        x = torch.randn(1, 3, 224, 224)
        m.encode_image(x)
        return _torch_seconds_per_call(lambda: m.encode_image(x))
    return ours, torch_baseline


def bench_s3d(batch: int = 8, stack: int = 64, iters: int = 10):
    """(64f stacks/sec, torch secs/stack or None) — the reference's default
    s3d work unit (configs/s3d.yml stack_size=64 at 224px)."""
    import jax
    import jax.numpy as jnp
    from video_features_tpu.extractors.s3d import _device_forward
    from video_features_tpu.models import s3d as s3d_m
    from video_features_tpu.parallel.mesh import cast_floating

    model = s3d_m.S3D(num_classes=400)
    params = cast_floating(s3d_m.init_params(), jnp.bfloat16)
    step = jax.jit(lambda p, x: _device_forward(model, jnp.bfloat16, True,
                                                p, x))
    rng = np.random.default_rng(0)
    data = [jax.device_put(rng.integers(
        0, 255, size=(batch, stack, 224, 224, 3), dtype=np.uint8))
        for _ in range(2)]
    _record_cost("s3d", step, (params, data[0]))
    ours = _device_rate(step, [(params, d) for d in data], batch, iters)

    def torch_baseline():
        import torch
        mod = _load_ref_module("ref_s3d", "models/s3d/s3d_src/s3d.py")
        if mod is None:
            return None
        m = mod.S3D(num_class=400).eval()
        x = torch.randn(1, 3, stack, 224, 224)
        m(x)
        return _torch_seconds_per_call(lambda: m(x))
    return ours, torch_baseline


def bench_vggish(batch: int = 256, iters: int = 20):
    """(0.96s log-mel examples/sec through the VGG tower, torch secs)."""
    import jax
    import jax.numpy as jnp
    from video_features_tpu.extractors.vggish import _device_forward
    from video_features_tpu.models import vggish as vggish_m
    from video_features_tpu.parallel.mesh import cast_floating

    model = vggish_m.VGGish()
    params = cast_floating(vggish_m.init_params(), jnp.bfloat16)
    step = jax.jit(lambda p, x: _device_forward(model, jnp.bfloat16, p, x))
    rng = np.random.default_rng(0)
    data = [jax.device_put(rng.standard_normal(
        (batch, 96, 64, 1)).astype(np.float32)) for _ in range(2)]
    _record_cost("vggish", step, (params, data[0]))
    ours = _device_rate(step, [(params, d) for d in data], batch, iters)

    def torch_baseline():
        import torch
        _tests_on_path()
        from torch_oracles import TorchVGGish
        m = TorchVGGish().eval()
        x = torch.randn(1, 1, 96, 64)
        m(x)
        return _torch_seconds_per_call(lambda: m(x))
    return ours, torch_baseline


#: (f32_rate, bf16_rate, torch_baseline_fn) per flow family — each pair
#: measured INTERLEAVED in one _device_rate_ab call, cached so the two
#: bench rows share one measurement
_FLOW_PAIRS = {}


def _raft_standalone_pair():
    """Standalone raft extractor work unit (20 GRU iterations at the
    sample video's geometry, batch 32): f32 with the extractor's matmul-
    precision pin (the flow field IS the output) and the opt-in
    precision=bfloat16 mode (~0.1 px drift), interleaved. Geometry is
    fixed (the cache is keyed by family only)."""
    if "raft" in _FLOW_PAIRS:
        return _FLOW_PAIRS["raft"]
    batch, h, w, iters = 32, 240, 320, 10
    import jax
    import jax.numpy as jnp
    from video_features_tpu.extractors.raft import _raft_forward
    from video_features_tpu.models import raft as raft_m
    from video_features_tpu.parallel.mesh import cast_floating

    params = raft_m.init_params()
    rng = np.random.default_rng(0)
    data = [jax.device_put(rng.integers(
        0, 255, size=(batch, 2, h, w, 3), dtype=np.uint8))
        for _ in range(2)]

    m32 = raft_m.RAFT(iters=raft_m.ITERS, dtype=jnp.float32)
    # the f32 extractor pins matmul precision globally (base.py); bake the
    # pin into THIS step only, at trace time
    step32 = jax.jit(lambda p, x: _with_highest(_raft_forward, m32, p, x))
    m16 = raft_m.RAFT(iters=raft_m.ITERS, dtype=jnp.bfloat16)
    p16 = cast_floating(params, jnp.bfloat16)
    # pin "default" at trace time too: an extractor constructed earlier in
    # the same process sets the GLOBAL highest-precision config
    # (extractors/base.py), which would silently upcast this variant
    step16 = jax.jit(lambda p, x: _with_default(_raft_forward, m16, p, x))

    _record_cost("raft_f32", step32, (params, data[0]))
    _record_cost("raft_bf16", step16, (p16, data[0]))
    f32_v, bf16_v = _device_rate_ab(
        [(step32, [(params, d) for d in data]),
         (step16, [(p16, d) for d in data])], batch, iters)

    def torch_baseline():
        import torch
        path = _ref_path("models/raft/raft_src/raft.py")
        if path is None:
            return None
        mod = _load_ref_module("ref_raft_sa", "models/raft/raft_src/raft.py")
        m = mod.RAFT().eval()
        x = torch.randint(0, 255, (1, 3, h, w), dtype=torch.float32)
        with torch.no_grad():
            m(x, x, iters=2)
        return _torch_seconds_per_call(
            lambda: m(x, x, iters=20, test_mode=True))

    _FLOW_PAIRS["raft"] = (f32_v, bf16_v, torch_baseline)
    return _FLOW_PAIRS["raft"]


def _with_highest(fn, *args):
    import jax
    with jax.default_matmul_precision("highest"):
        return fn(*args)


def _with_default(fn, *args):
    import jax
    with jax.default_matmul_precision("default"):
        return fn(*args)


def _pwc_standalone_pair():
    """(flow fields/sec; torch baseline None BY CONSTRUCTION — the
    reference PWC correlation is a CUDA-only CuPy kernel and cannot run on
    this host at all, models/pwc/pwc_src/correlation.py. That this chain
    runs on TPU without a second conda env is itself the parity win.)
    f32 default and the opt-in precision=bfloat16 mode (0.015 px drift),
    interleaved at batch 32 @256x448 (cache keyed by family only)."""
    if "pwc" in _FLOW_PAIRS:
        return _FLOW_PAIRS["pwc"]
    batch, h, w, iters = 32, 256, 448, 10
    import jax
    import jax.numpy as jnp
    from video_features_tpu.extractors.pwc import _pwc_forward
    from video_features_tpu.models import pwc as pwc_m

    params = pwc_m.init_params()
    rng = np.random.default_rng(0)
    data = [jax.device_put(rng.integers(
        0, 255, size=(batch, 2, h, w, 3), dtype=np.uint8))
        for _ in range(2)]
    m32 = pwc_m.PWCNet(dtype=jnp.float32)
    m16 = pwc_m.PWCNet(dtype=jnp.bfloat16)
    # pin each variant's trace-time matmul precision to its production
    # extractor config, independent of ambient global state
    step32 = jax.jit(lambda p, x: _with_highest(_pwc_forward, m32, p, x))
    step16 = jax.jit(lambda p, x: _with_default(_pwc_forward, m16, p, x))
    args = [(params, d) for d in data]
    _record_cost("pwc_f32", step32, args[0])
    _record_cost("pwc_bf16", step16, args[0])
    f32_v, bf16_v = _device_rate_ab(
        [(step32, args), (step16, args)], batch, iters)
    _FLOW_PAIRS["pwc"] = (f32_v, bf16_v, None)
    return _FLOW_PAIRS["pwc"]


def main() -> None:
    import jax
    platform = jax.devices()[0].platform

    ours = bench_ours()
    try:
        theirs = bench_torch_reference()
        r21d_ratio = ours / theirs
    except Exception:
        r21d_ratio = None

    # never lose the already-measured r21d headline to an I3D-side failure
    # (the RAFT scan's cold compile is the realistic way bench_i3d_ours
    # can die)
    try:
        i3d = bench_i3d_ours()
    except Exception as e:
        print(f"WARNING: i3d bench failed: {type(e).__name__}: {e}",
              file=sys.stderr)
        i3d = None
    try:
        i3d_bf = bench_i3d_ours(raft_bf16=True) if i3d is not None else None
    except Exception as e:
        print(f"WARNING: i3d bf16-raft bench failed: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        i3d_bf = None
    try:
        i3d_pwc = bench_i3d_pwc_ours()
    except Exception as e:
        print(f"WARNING: i3d pwc bench failed: {type(e).__name__}: {e}",
              file=sys.stderr)
        i3d_pwc = None
    i3d_torch = None
    if i3d is not None:
        try:
            i3d_torch = bench_i3d_torch()
        except Exception:
            i3d_torch = None

    r21d_entry = {
        "metric": f"r2plus1d_18 16f@112px clip throughput ({platform}, bf16)",
        "value": round(ours, 2),
        "unit": "clips/sec/chip",
        "vs_baseline": round(r21d_ratio, 2) if r21d_ratio is not None else None,
        "baseline": BASELINE_DESC,
        "note": "program unchanged since round 3: a delta vs BENCH_r03 "
                "is run-to-run spread (no cross-binary interleaved A/B "
                "was run; docs/performance.md measurement discipline)",
        # device-efficiency fields (ISSUE 12): XLA-cost-model FLOPs x
        # measured rate / peak registry — under the bench-history gate
        **_roofline_fields(f"r21d_b{BATCH}", ours, BATCH),
    }
    metrics = [r21d_entry]
    # the bf16-raft row is the precision=bfloat16 flow-stream mode: flow
    # drift ~0.1 px stays under the ToUInt8 quantization step, so it is
    # the fast production configuration of the same work unit
    i3d_note = ("round-4 step: fused lookup+convc1 kernel + 4 stacks/RAFT-"
                "forward. The +48% vs BENCH_r03 was established INTERLEAVED "
                "in one process (round-3 "
                "config 3.94 vs round-4 6.34 stacks/s, medians of 4 "
                "alternating rounds); this row is the sequential re-run")
    pwc_note = ("round-5: the DEFAULT i3d config (flow_type=pwc, as in the "
                "reference) finally measured AND optimized: bf16 PWC conv "
                "stacks (models/pwc.py dtype; flow/warp math f32, 0.015 px "
                "drift) + 4 stacks/forward. Interleaved A/B medians: "
                "raft-s4f 6.28 / pwc-f32 5.86 / "
                "pwc-bf16x4 12.08 stacks/s — pwc default is now measured, "
                "not inherited")
    for label, value, flow_kind, cost_key, note in (
            ("bf16 i3d / f32 raft", i3d, "raft", "i3d_raft", i3d_note),
            ("bf16 i3d + bf16 raft", i3d_bf, "raft", "i3d_raft_bf16",
             i3d_note),
            ("bf16 i3d + bf16 pwc, DEFAULT config", i3d_pwc, "pwc",
             "i3d_pwc", pwc_note)):
        if value is None:
            continue
        # the torch baseline runs the reference's RAFT flow; a PWC-flow
        # ratio against it would be a cross-model comparison, not the
        # same-work-unit claim BASELINE_DESC makes
        ratio = (value / i3d_torch
                 if flow_kind == "raft" and i3d_torch else None)
        metrics.append({
            "metric": f"i3d rgb+flow({flow_kind}) {I3D_STACK}f@{I3D_SIDE}px "
                      f"stack throughput ({platform}, {label})",
            "value": round(value, 3),
            "unit": "stacks/sec/chip",
            "vs_baseline": round(ratio, 2) if ratio is not None else None,
            "baseline": BASELINE_DESC,
            "note": note,
            **_roofline_fields(cost_key, value, 4),
        })

    # ---- per-family rows (round-4: every family gets a number) ----------
    families = [
        # round-5 interleaved batch scan (5 alternating rounds, medians):
        # B=128 1280 / B=256 1333 / B=512 1400 clips/s — wider batches
        # keep amortizing the C=144/64 channel-tile edges (performance.md
        # MFU breakdown). Headline row stays B=128 for cross-round
        # comparability; this row records the wider-batch ceiling.
        ("r2plus1d_18 16f@112px clip throughput, B=512 wide-batch",
         lambda: (bench_ours(batch=512), None), "clips/sec/chip", None,
         ("r21d_b512", 512)),
        ("resnet50 224px frame throughput", bench_resnet50,
         "frames/sec/chip", None, ("resnet50", 128)),
        ("clip ViT-B/32 224px frame throughput", bench_clip_vit_b32,
         "frames/sec/chip", None, ("clip", 128)),
        ("s3d 64f@224px stack throughput", bench_s3d,
         "stacks/sec/chip", None, ("s3d", 8)),
        ("vggish 0.96s log-mel example throughput", bench_vggish,
         "examples/sec/chip", None, ("vggish", 256)),
        # the f32/bf16 pairs below come from ONE interleaved measurement
        # each (_device_rate_ab)
        ("raft sintel 20-iter flow @240x320 (f32, matmul=highest)",
         lambda: (_raft_standalone_pair()[0], _raft_standalone_pair()[2]),
         "pairs/sec/chip", None, ("raft_f32", 32)),
        # bf16 raft: no torch ratio — the baseline is f32 numerics, and
        # the f32 row above already carries it for the same work unit
        ("raft sintel 20-iter flow @240x320 (opt-in precision=bfloat16, "
         "~0.1 px drift)",
         lambda: (_raft_standalone_pair()[1], None),
         "pairs/sec/chip", "interleaved with the f32 row",
         ("raft_bf16", 32)),
        ("pwc flow @256x448 (f32, standalone default)",
         lambda: (_pwc_standalone_pair()[0], None), "pairs/sec/chip",
         "no torch-cpu baseline EXISTS: the reference PWC correlation is "
         "a CUDA-only CuPy kernel (models/pwc/pwc_src/correlation.py); "
         "running at all without a GPU/second conda env is the parity "
         "delta. Cross-ROUND deltas on this row were never interleaved; "
         "the f32-vs-bf16 pair below is", ("pwc_f32", 32)),
        ("pwc flow @256x448 (opt-in precision=bfloat16, 0.015 px drift)",
         lambda: (_pwc_standalone_pair()[1], None), "pairs/sec/chip",
         "interleaved with the f32 row", ("pwc_bf16", 32)),
    ]
    for name, fn, unit, note, cost in families:
        try:
            value, torch_fn = fn()
        except Exception as e:
            print(f"WARNING: {name} bench failed: {type(e).__name__}: {e}",
                  file=sys.stderr)
            continue
        ratio = None
        if torch_fn is not None:
            try:
                secs = torch_fn()  # seconds per ONE work unit, batch=1
                ratio = value * secs if secs is not None else None
            except Exception as e:
                print(f"WARNING: {name} torch baseline failed: "
                      f"{type(e).__name__}: {e}", file=sys.stderr)
        row = {
            "metric": f"{name} ({platform}, bf16)"
            if "f32" not in name else f"{name} ({platform})",
            "value": round(value, 2),
            "unit": unit,
            "vs_baseline": round(ratio, 2) if ratio is not None else None,
            "baseline": BASELINE_DESC if ratio is not None else None,
        }
        if note:
            row["note"] = note
        if cost is not None:
            row.update(_roofline_fields(cost[0], value, cost[1]))
        metrics.append(row)
    # sustained real-pipeline number (decode -> device -> sink): the
    # deliverable throughput next to the device-only steady state;
    # wall-clock includes the one-time compile when the persistent cache
    # is cold, so cache warmth (the two device benches above) matters
    try:
        pipe = bench_pipeline()
        row = {
            "metric": "r2plus1d_18 sustained pipeline decode->device->sink",
            "value": round(pipe["clips_per_s"], 2),
            "unit": "clips/sec",
            "vs_baseline": None,
            # a real field, not prose in the metric name, so the compact
            # line's truncation can never drop it
            "videos_per_s": round(pipe["videos_per_s"], 2),
            "note": "8x sample video, yuv420+bf16, cross-video B=128, "
                    "video_workers=auto (the recorded configuration)",
        }
        if pipe.get("stages"):
            # the roofline attribution rides the row: per-stage ms +
            # X-bound verdict from the run's own trace
            row["stages"] = pipe["stages"]
        metrics.append(row)
    except Exception as e:
        print(f"WARNING: pipeline bench failed: {type(e).__name__}: {e}",
              file=__import__("sys").stderr)
    # decode-once fan-out: N families for ~1x decode; recorded every
    # round so the sharing ratio is tracked alongside the device numbers
    try:
        share = bench_shared_decode()
        metrics.append({
            "metric": "multi-family shared-decode sharing ratio "
                      f"({'+'.join(share['families'])})",
            "value": share["sharing_ratio"],
            "unit": "x vs sequential single-family runs",
            "vs_baseline": None,
            "sequential_s": share["sequential_s"],
            "shared_s": share["shared_s"],
            "note": f"{share['n_copies']}x sample, extraction_fps=4, "
                    "fresh outputs, warmed; decode-bound hosts approach "
                    "Nx — scripts/throughput.py --families runs the "
                    "interleaved-median A/B (docs/performance.md "
                    "'Decode once, extract many')",
        })
    except Exception as e:
        print(f"WARNING: shared-decode bench failed: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
    # trace=true wall-clock tax on the same smoke corpus: the ISSUE-4
    # acceptance bar is <= 1.05x, tracked per round like the sharing ratio
    try:
        tro = bench_trace_overhead()
        metrics.append({
            "metric": "pipeline tracing overhead (trace=true vs off, "
                      f"{'+'.join(tro['families'])})",
            "value": tro["overhead_ratio"],
            "unit": "x wall-clock",
            "vs_baseline": None,
            "off_s": tro["off_s"],
            "on_s": tro["on_s"],
            "note": f"{tro['n_copies']}x sample, extraction_fps=4, warmed, "
                    "fresh outputs; per-frame stage spans + fan-out "
                    "backpressure accounting are the instrumented hot "
                    "paths (docs/observability.md 'Reading the pipeline "
                    "timeline')",
        })
    except Exception as e:
        print(f"WARNING: trace-overhead bench failed: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
    # health=true wall-clock tax (telemetry/health.py digests at the sink
    # boundary): same <= 1.05x acceptance bar as the trace ratio, tracked
    # per round; scripts/bench_history.py flags it when it creeps
    try:
        ho = bench_health_overhead()
        metrics.append({
            "metric": "output health overhead (health=true vs off, "
                      f"{'+'.join(ho['families'])})",
            "value": ho["overhead_ratio"],
            "unit": "x wall-clock",
            "vs_baseline": None,
            "off_s": ho["off_s"],
            "on_s": ho["on_s"],
            "note": f"{ho['n_copies']}x sample, extraction_fps=4, warmed, "
                    "fresh outputs; per-feature digests (stats + sha256 "
                    "content signature) at the sink boundary are the "
                    "instrumented path (docs/observability.md 'Output "
                    "health & comparing runs')",
        })
    except Exception as e:
        print(f"WARNING: health-overhead bench failed: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
    # parity=true wall-clock tax (telemetry/parity.py seam digests): the
    # sixth observability knob held to the same <= 1.05x budget,
    # bench-history gated — the off path must stay one global read
    try:
        po = bench_parity_overhead()
        metrics.append({
            "metric": "parity observatory overhead (parity=true vs off, "
                      f"{'+'.join(po['families'])})",
            "value": po["overhead_ratio"],
            "unit": "x wall-clock",
            "vs_baseline": None,
            "off_s": po["off_s"],
            "on_s": po["on_s"],
            "note": f"{po['n_copies']}x sample, extraction_fps=4, warmed, "
                    "fresh outputs; decode/transform digests in the "
                    "TransformTap wrapper (bounded per seam/key) plus "
                    "backbone/head digests at the batch boundary are the "
                    "instrumented paths (docs/numerics.md)",
        })
    except Exception as e:
        print(f"WARNING: parity-overhead bench failed: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
    # roofline accounting (telemetry/roofline.py): one AOT lowering per
    # program shape + a dict hit per dispatch + the chained stage hook —
    # the fifth always-on observability knob held to the same <= 1.05x
    # budget, bench-history gated
    try:
        rfo = bench_roofline_overhead()
        metrics.append({
            "metric": "roofline accounting overhead (roofline=true vs "
                      f"off, {'+'.join(rfo['families'])})",
            "value": rfo["overhead_ratio"],
            "unit": "x wall-clock",
            "vs_baseline": None,
            "off_s": rfo["off_s"],
            "on_s": rfo["on_s"],
            "note": f"{rfo['n_copies']}x sample, extraction_fps=4, warmed "
                    "(incl. the device peak cache), fresh outputs; cost "
                    "cards lower once per (runner, batch shape), every "
                    "further dispatch is a dict hit (docs/observability.md "
                    "'The roofline pillar')",
        })
    except Exception as e:
        print(f"WARNING: roofline-overhead bench failed: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
    # fault-injection sites (utils/inject.py): the off path is permanent
    # production code on the sink/decode/queue hot paths, so its cost is
    # tracked per round exactly like trace=/health= — armed-but-quiet vs
    # off, <= 1.05x budget, bench-history gated
    try:
        io_ = bench_inject_overhead()
        metrics.append({
            "metric": "fault-injection overhead (armed-quiet vs off, "
                      f"{'+'.join(io_['families'])})",
            "value": io_["overhead_ratio"],
            "unit": "x wall-clock",
            "vs_baseline": None,
            "off_s": io_["off_s"],
            "on_s": io_["on_s"],
            "note": f"{io_['n_copies']}x sample, extraction_fps=4, warmed, "
                    "fresh outputs; armed plan with unreachable triggers "
                    "pays per-hit counting + the python atomic sink path "
                    "(docs/chaos.md) — off is one global read per site",
        })
    except Exception as e:
        print(f"WARNING: inject-overhead bench failed: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
    # fleet ops plane (ISSUE 10): request-id correlation reads on every
    # emitter + the serve SLO histogram path, vs the stock run — the
    # fourth always-on knob held to the same <= 1.05x budget
    try:
        so = bench_slo_overhead()
        metrics.append({
            "metric": "serve SLO + request-id instrumentation overhead "
                      f"(correlated vs off, {'+'.join(so['families'])})",
            "value": so["overhead_ratio"],
            "unit": "x wall-clock",
            "vs_baseline": None,
            "off_s": so["off_s"],
            "on_s": so["on_s"],
            "note": f"{so['n_copies']}x sample, extraction_fps=4, warmed, "
                    "fresh outputs; on = telemetry+health under an armed "
                    "request context (telemetry/context.py), the "
                    "serve-grade stamping path — off is one thread-local "
                    "read per emitter (docs/observability.md 'One view "
                    "of the fleet')",
        })
    except Exception as e:
        print(f"WARNING: SLO-overhead bench failed: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
    # alerting & flight recorder (ISSUE 13): per-tick history sampling +
    # a full quiet rule-engine evaluation on the heartbeat cadence — the
    # sixth always-on observability knob held to the same <= 1.05x
    # budget, bench-history gated
    try:
        ao = bench_alert_overhead()
        metrics.append({
            "metric": "alerting + history overhead (alerts=true vs "
                      f"telemetry-only, {'+'.join(ao['families'])})",
            "value": ao["overhead_ratio"],
            "unit": "x wall-clock",
            "vs_baseline": None,
            "off_s": ao["off_s"],
            "on_s": ao["on_s"],
            "note": f"{ao['n_copies']}x sample, extraction_fps=4, warmed, "
                    "fresh outputs, 1s heartbeat in BOTH arms; on = "
                    "history sampling + a quiet rule evaluation per tick "
                    "(no rule fires, nothing captured) — the steady-state "
                    "watching cost (docs/observability.md 'Alerting & "
                    "incident bundles')",
        })
    except Exception as e:
        print(f"WARNING: alert-overhead bench failed: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
    # storage lifecycle accounting (gc.py): per-plane tree walk + gauge
    # publication on a worst-case 1s cadence — the accounting half of
    # vft-gc held to the same <= 1.05x budget, bench-history gated
    try:
        go = bench_gc_overhead()
        metrics.append({
            "metric": "gc accounting overhead (gc=true vs "
                      f"telemetry-only, {'+'.join(go['families'])})",
            "value": go["overhead_ratio"],
            "unit": "x wall-clock",
            "vs_baseline": None,
            "off_s": go["off_s"],
            "on_s": go["on_s"],
            "note": f"{go['n_copies']}x sample, extraction_fps=4, warmed, "
                    "fresh outputs, 1s heartbeat in BOTH arms; on = a "
                    "full per-plane usage walk + vft_gc_* gauges every "
                    "interval (1s here, 300s production default) — "
                    "eviction runs in vft-gc's own process, never here "
                    "(docs/storage.md)",
        })
    except Exception as e:
        print(f"WARNING: gc-overhead bench failed: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
    # repeat-content avoidance (cache.py): second pass over the same
    # corpus must be near-pure cache-hit throughput; tracked per round
    # under the bench-history regression gate like the sharing ratio
    try:
        ca = bench_cache()
        row = {
            "metric": f"feature-cache warm-pass ratio ({ca['family']}, "
                      "2nd pass over same corpus)",
            "value": ca["speedup"],
            "unit": "x speedup, cold pass over cache-hit pass",
            "vs_baseline": None,
            "cold_s": ca["cold_s"],
            "warm_s": ca["warm_s"],
            "note": f"{ca['n_copies']}x sample, extraction_fps=4, compiles "
                    "warmed untimed, outputs verified bit-identical; the "
                    "warm pass's own trace shows the decode/device stages "
                    "near zero (docs/performance.md 'Never compute "
                    "twice')",
        }
        if ca.get("warm_stages"):
            row["warm_stages"] = ca["warm_stages"]
        metrics.append(row)
    except Exception as e:
        print(f"WARNING: cache bench failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    # fleet scheduling (parallel/queue.py): static hash-shard vs
    # work-stealing makespan under injected 4x skew, tracked per round
    # under the bench-history gate; the same bench verifies exactly-once
    # + bit-identity with real queue workers before publishing the ratio
    try:
        fl = bench_fleet()
        metrics.append({
            "metric": "fleet work-stealing vs static hash-shard makespan "
                      "(simulated 4x skew)",
            "value": fl["makespan_ratio"],
            "unit": "x static makespan over queue makespan",
            "vs_baseline": None,
            "static_makespan_s": fl["static_makespan_s"],
            "queue_makespan_s": fl["queue_makespan_s"],
            "note": f"{fl['corpus']}, {fl['n_hosts']} simulated hosts, "
                    "oversized item named to sort (claim) first; sleeps "
                    "as work so N workers overlap on a 1-core bench host "
                    "and the delta is pure scheduling. Real-worker half: "
                    f"{fl['real_videos']} videos x 2 fleet=queue CLI "
                    "processes verified bit-identical to fleet=static "
                    "with one done marker each (docs/fleet.md)",
        })
    except Exception as e:
        print(f"WARNING: fleet bench failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    # warm-start plane (ISSUE 11): join latency as a number — cold
    # process vs warm process over the fleet compile store, features
    # bit-identical, tracked per round under the bench-history gate
    try:
        cs = bench_coldstart()
        metrics.append({
            "metric": "compile-cache warm-start first-inference speedup "
                      f"({cs['family']}, fresh process)",
            "value": cs["speedup"],
            "unit": "x cold first-inference over warm",
            "vs_baseline": None,
            "cold_s": cs["cold_s"], "warm_s": cs["warm_s"],
            "note": f"cold pass compiled {cs['cold_compiles']} program(s); "
                    f"warm pass {cs['warm_hits']} hits / "
                    f"{cs['warm_misses']} misses, outputs bit-identical; "
                    "cli wall timed in-subprocess, imports excluded "
                    "(docs/performance.md 'Never compile twice, fleet "
                    "edition')",
        })
    except Exception as e:
        print(f"WARNING: coldstart bench failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    # preemptible churn (ISSUE 11): makespan degradation under
    # worker.kill@p with respawns, warm-start on; lower is better, so
    # the row is named as an overhead for the bench-history direction
    try:
        fc = bench_fleet_churn()
        pts = ", ".join(f"p={p['rate']}: {p['makespan_s']}s"
                        f" ({p['kills']} kills)" for p in fc["curve"])
        wc = fc["warm_vs_cold_at_mid"]
        metrics.append({
            "metric": "fleet churn makespan overhead (worker.kill@p="
                      f"{fc['curve'][-1]['rate']} vs churn-free, "
                      "warm-start)",
            "value": fc["degradation_at_max"],
            "unit": "x churn-free makespan",
            "vs_baseline": None,
            "curve": fc["curve"],
            "warm_vs_cold_at_mid": wc,
            "note": f"{fc['n_videos']} videos x {fc['n_workers']} queue "
                    f"workers, killed workers respawned; curve: {pts}; "
                    f"warm-start removed {wc['rejoin_penalty_removed_s']}s "
                    f"vs compile_cache=false at p={wc['rate']}; every run "
                    "auditor-PASS (docs/fleet.md 'Elastic capacity')",
        })
    except Exception as e:
        print(f"WARNING: fleet churn bench failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    # ROADMAP-5 tail: the FLEET sustained rate (N queue workers x shared
    # decode x warm compile cache) recorded next to the single-host
    # sustained row, which additionally carries it as a field
    try:
        fs = bench_fleet_sustained()
        metrics.append({
            "metric": "fleet sustained extraction rate "
                      f"({fs['n_workers']} queue workers x shared decode "
                      "x warm compile cache)",
            "value": fs["extractions_per_s"],
            "unit": "extractions/sec (fleet)",
            "vs_baseline": None,
            "videos_per_s": fs["videos_per_s"],
            "note": f"{fs['n_videos']} distinct synthetic clips x "
                    f"{'+'.join(fs['families'])}, fleet=queue, drain-loop "
                    "walls (imports/attach excluded); on this 1-core "
                    "container the workers time-slice one CPU — the row "
                    "records the system configuration so multi-core/TPU "
                    "rounds measure scaling against it",
        })
        for r in metrics:
            if r.get("metric", "").startswith("r2plus1d_18 sustained"):
                # the satellite contract: the sustained row itself also
                # records the fleet-configuration rate
                r["fleet"] = {k: fs[k] for k in
                              ("n_workers", "families", "videos_per_s",
                               "extractions_per_s", "fleet_makespan_s")}
    except Exception as e:
        print(f"WARNING: fleet sustained bench failed: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
    # recorded traffic drill (loadgen.py): the fixed burst_shed scenario
    # end to end — gateway HTTP admission, spool protocol, journal join,
    # audit gate — as wall seconds per drill; regressions in any of
    # those layers move this row, and a FAIL verdict voids it
    try:
        sc = bench_scenario()
        if sc["verdict"] != "PASS":
            raise RuntimeError(
                f"drill verdict {sc['verdict']} (audit_pass="
                f"{sc['audit_pass']}, attainment={sc['attainment_pct']})")
        metrics.append({
            "metric": f"scenario drill wall seconds ({sc['scenario']}, "
                      f"{sc['virtual_s']:.0f} virtual s @ "
                      f"x{sc['speedup']:.0f}, stubbed video step)",
            "value": sc["wall_s"],
            "unit": "s per drill",
            "vs_baseline": None,
            "offered": sc["offered"],
            "admitted": sc["admitted"],
            "rejected": sc["rejected"],
            "note": f"seed {sc['seed']}: {sc['offered']} offered -> "
                    f"{sc['admitted']} admitted / {sc['rejected']} 429 / "
                    f"{sc['completed']} completed, verdict PASS, "
                    f"attainment {sc['attainment_pct']}; the whole "
                    "observatory round trip incl. vft-audit and the "
                    "_scenario.json join (docs/scenarios.md)",
        })
    except Exception as e:
        print(f"WARNING: scenario bench failed: {type(e).__name__}: {e}",
              file=sys.stderr)

    # one JSON line: headline fields stay the r21d config (driver contract
    # since round 1); "metrics" carries the north-star configs + pipeline,
    # compacted (no note/baseline prose, row 1 deduped into the top level)
    # so the WHOLE line fits in the driver's 2,000-char tail capture
    seen_names = set()

    def compact(row):
        # "unit" and "effective_tflops" are dropped from the line: the
        # 2,000-char driver tail was already at 1,942 before the roofline
        # fields, and every direction-of-goodness case bench_history
        # handles survives on the metric NAME alone (overhead rows all
        # say "overhead"; mfu is its own keep so per-row device
        # efficiency stays under the regression gate — effective_tflops
        # is mfu x a per-device constant, so guarding one guards both)
        out = {k: v for k, v in row.items()
               if k in ("metric", "value", "vs_baseline",
                        "videos_per_s", "mfu")
               and v is not None}
        # 60-char cap keeps the WHOLE line inside the driver's 2,000-char
        # tail as rows accumulate. On a
        # truncation collision (the two i3d raft rows share a 60-char
        # prefix) the cap extends until the name is unique again.
        cap = 60
        name = out["metric"][:cap]
        while name in seen_names and cap < len(out["metric"]):
            cap += 10
            name = out["metric"][:cap]
        seen_names.add(name)
        out["metric"] = name
        return out
    line = {**compact(metrics[0]),
            # the driver contract names all four headline keys, so
            # vs_baseline stays present even when the torch baseline failed
            "vs_baseline": r21d_entry["vs_baseline"],
            "metrics": [compact(r) for r in metrics[1:]]}
    print(json.dumps(line))


if __name__ == "__main__":
    # `python bench.py bench_cache` (or any other bench_* function): run
    # just that bench and print its JSON — the full-round main() takes
    # tens of minutes, single rows shouldn't
    if len(sys.argv) > 1:
        name = sys.argv[1]
        fn = globals().get(name)
        if not callable(fn) or not name.startswith("bench_"):
            raise SystemExit(
                f"unknown bench {name!r}; pick one of: " + ", ".join(
                    sorted(n for n, v in globals().items()
                           if n.startswith("bench_") and callable(v))))
        print(json.dumps(fn()))
    else:
        main()
