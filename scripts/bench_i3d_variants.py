#!/usr/bin/env python
"""Interleaved A/B harness for the I3D RGB+Flow step (round-4 perf axis).

Variants are the VERDICT round-4 levers for the plateaued I3D axis:

  - ``s1`` — the bench.py step exactly (1 stack = 64 RAFT pairs/forward);
  - ``s2`` / ``s4`` — 2/4 stacks per forward (128/256 pairs), amortizing
    per-launch / per-scan-iteration fixed costs across more queries;
  - an ``f`` suffix (``s1f``, ``s2f``) — the fused lookup+convc1 kernel
    (VFT_FUSE_CONVC1, models/raft.py); without it the round-3 per-level
    unfused kernels run.

Methodology (docs/performance.md): every trial round runs ALL variants
back-to-back and the report compares per-variant MEDIANS across rounds, so
that drift over a run falls on every variant alike.
Completion is fenced with a D2H read (`settle`); inputs are staged on
device before timing.

Usage:
    python scripts/bench_i3d_variants.py [--rounds 5] [--iters 6]
        [--variants s1,s2,s4] [--trace DIR --trace-variant s1]
"""
import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402

I3D_SIDE = 224
STACK = 64


def build_step(n_stacks: int):
    """Jitted step over (n_stacks, STACK+1, H, W, 3) uint8: RAFT flow on the
    n_stacks*STACK pair batch + both I3D tower forwards (bf16 everywhere —
    the production precision=bfloat16 configuration, bench.py's headline
    i3d row)."""
    import jax
    import jax.numpy as jnp
    from video_features_tpu.extractors.i3d import _i3d_forward
    from video_features_tpu.extractors.i3d_flow import _crop_quantize
    from video_features_tpu.models import i3d as i3d_m, raft as raft_m
    from video_features_tpu.parallel.mesh import cast_floating

    model = i3d_m.I3D(num_classes=400)
    raft = raft_m.RAFT(iters=raft_m.ITERS, dtype=jnp.bfloat16)
    params = dict(
        rgb=cast_floating(i3d_m.init_params("rgb"), jnp.bfloat16),
        flow=cast_floating(i3d_m.init_params("flow"), jnp.bfloat16),
        raft=cast_floating(raft_m.init_params(), jnp.bfloat16),
    )

    @jax.jit
    def step(p, stacks_u8):
        # stacks_u8: (S, STACK+1, H, W, 3) uint8. All S stacks' pairs fold
        # into ONE RAFT pair batch; the I3D towers run batch=S.
        s = stacks_u8.shape[0]
        pairs = jnp.stack([stacks_u8[:, :-1], stacks_u8[:, 1:]], axis=2)
        pairs = pairs.reshape(s * STACK, 2, I3D_SIDE, I3D_SIDE, 3)
        flow = raft_m.padded_flow(raft, p["raft"],
                                  pairs.astype(jnp.float32))[0]
        quant = _crop_quantize(flow, I3D_SIDE)
        quant = quant.reshape(s, STACK, I3D_SIDE, I3D_SIDE, 2)
        rgb = _i3d_forward(model, jnp.bfloat16, True, p["rgb"],
                           stacks_u8[:, :-1].astype(jnp.float32))
        flo = _i3d_forward(model, jnp.bfloat16, True, p["flow"], quant)
        return rgb, flo

    return step, params


def build_step_pwc(n_stacks: int, pwc_bf16: bool = False):
    """I3D RGB+Flow step with PWC flow instead of RAFT — the reference's
    DEFAULT i3d configuration (reference configs/i3d.yml:6 flow_type: pwc),
    unbenchmarked until round 5 (VERDICT r4 weak #5). Same work unit as
    build_step: (S, STACK+1, 224, 224, 3) uint8 -> both tower features."""
    import jax
    import jax.numpy as jnp
    from video_features_tpu.extractors.i3d import _i3d_forward
    from video_features_tpu.extractors.i3d_flow import _crop_quantize
    from video_features_tpu.models import i3d as i3d_m, pwc as pwc_m
    from video_features_tpu.parallel.mesh import cast_floating

    model = i3d_m.I3D(num_classes=400)
    pwc = pwc_m.PWCNet(dtype=jnp.bfloat16 if pwc_bf16 else jnp.float32)
    params = dict(
        rgb=cast_floating(i3d_m.init_params("rgb"), jnp.bfloat16),
        flow=cast_floating(i3d_m.init_params("flow"), jnp.bfloat16),
        pwc=pwc_m.init_params(),
    )

    @jax.jit
    def step(p, stacks_u8):
        s = stacks_u8.shape[0]
        pairs = jnp.stack([stacks_u8[:, :-1], stacks_u8[:, 1:]], axis=2)
        pairs = pairs.reshape(s * STACK, 2, I3D_SIDE, I3D_SIDE, 3)
        x = pairs.astype(jnp.float32)
        flow = pwc.apply({"params": p["pwc"]}, x[:, 0], x[:, 1])
        quant = _crop_quantize(flow, I3D_SIDE)
        quant = quant.reshape(s, STACK, I3D_SIDE, I3D_SIDE, 2)
        rgb = _i3d_forward(model, jnp.bfloat16, True, p["rgb"],
                           stacks_u8[:, :-1].astype(jnp.float32))
        flo = _i3d_forward(model, jnp.bfloat16, True, p["flow"], quant)
        return rgb, flo

    return step, params


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--iters", type=int, default=6,
                    help="timed steps per variant per round")
    ap.add_argument("--variants", default="s1,s2,s4")
    ap.add_argument("--trace", default=None,
                    help="capture a jax.profiler trace of --trace-variant "
                         "into DIR (after warmup, --iters steps)")
    ap.add_argument("--trace-variant", default="s1")
    args = ap.parse_args()

    import jax
    from bench import _enable_cache_off_cpu
    from video_features_tpu.parallel.mesh import settle
    _enable_cache_off_cpu()

    names = [v.strip() for v in args.variants.split(",") if v.strip()]
    rng = np.random.default_rng(0)
    variants = {}
    import os
    import re
    for name in names:
        # sN[f][tTILE]: RAFT flow, stacks per forward, fused convc1, proj
        # tile override. pN[b]: PWC flow (the reference's default
        # flow_type), N stacks per forward, 'b' = bf16 PWC conv stacks.
        mp = re.fullmatch(r"p(\d+)(b?)", name)
        m = re.fullmatch(r"s(\d+)(f?)(?:t(\d+))?", name)
        if mp:
            step, params = build_step_pwc(int(mp.group(1)),
                                          pwc_bf16=bool(mp.group(2)))
            s = int(mp.group(1))
        elif m:
            s, fuse, tile = int(m.group(1)), bool(m.group(2)), m.group(3)
            # VFT_* knobs are read at TRACE time (models/raft.py,
            # kernels/corr_lookup.py), i.e. at the compile call below — set
            # them per variant, before first call
            os.environ["VFT_FUSE_CONVC1"] = "1" if fuse else "0"
            if tile:
                os.environ["VFT_PROJ_TILE_P"] = tile
            else:
                os.environ.pop("VFT_PROJ_TILE_P", None)
            step, params = build_step(s)
        else:
            raise SystemExit(f"bad variant {name!r}: expected sN[f][tTILE] "
                             "or pN[b]")
        data = [jax.device_put(rng.integers(
            0, 255, size=(s, STACK + 1, I3D_SIDE, I3D_SIDE, 3),
            dtype=np.uint8)) for _ in range(2)]
        t0 = time.perf_counter()
        settle(step(params, data[0]))  # compile
        print(f"[{name}] compiled in {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)
        settle(step(params, data[1]))  # warm
        variants[name] = (s, step, params, data)

    if args.trace:
        s, step, params, data = variants[args.trace_variant]
        with jax.profiler.trace(args.trace):
            for i in range(args.iters):
                out = step(params, data[i % 2])
            settle(out)
        print(f"trace ({args.trace_variant}, {args.iters} steps) -> "
              f"{args.trace}", file=sys.stderr)

    results = {n: [] for n in names}
    for r in range(args.rounds):
        for name in names:  # interleaved: every round touches every variant
            s, step, params, data = variants[name]
            t0 = time.perf_counter()
            for i in range(args.iters):
                out = step(params, data[i % 2])
            settle(out)
            dt = time.perf_counter() - t0
            results[name].append(s * args.iters / dt)
        print(f"round {r}: " + "  ".join(
            f"{n}={results[n][-1]:.3f}" for n in names), file=sys.stderr)

    report = {n: {"median_stacks_per_s": round(statistics.median(v), 3),
                  "all": [round(x, 3) for x in v]}
              for n, v in results.items()}
    print(json.dumps(report))


if __name__ == "__main__":
    main()
