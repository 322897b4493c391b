#!/usr/bin/env python3
"""Does today's code start on the chip? One TPU, five stages, one verdict.

    python chip_smoke.py                 # the contract run (needs a TPU)
    python chip_smoke.py --only 1,2      # a subset, while debugging
    python chip_smoke.py --rehearse      # the stage logic on the CPU, tiny

The main path is driven once through the entry points a user calls
(``python main.py ...``, ``python main.py serve ...``) at the YAML-default
width of the families every chip record holds, with seeded random weights:

  1. r21d cold at the YAML defaults (float32, 16 frames x 112 px, 8 clips);
  2. the same command in a fresh process: the compile cache must be found
     (zero misses, some hits);
  3. vft-serve (bfloat16) answering three spooled requests, the second and
     third without a compile;
  4. raft through the CLI at its defaults (bfloat16, 20 iterations, native
     240x320) - the one path on which a Pallas kernel runs;
  5. the RAFT program the extractor builds contains the Mosaic custom call,
     and both lookup kernels, compiled, agree with their XLA twins at the /8
     geometries the system produces.

A chip belongs to one process at a time, so this parent never imports JAX:
every stage is a child process run to its end before the next starts, and
every assertion reads a file the child wrote (``_run.json``, the response
JSONs, ``_telemetry.jsonl``, the ``.npy`` outputs) - never the exit code
alone, because per-video fault isolation makes a failed compile exit 0.

Stage lines report wall seconds, programs compiled and cache hits/misses.
They are set-up information for sizing a benchmark run, not metrics. The
full result is ``chiprun_out/chip_smoke/result.json``. The last stdout line
of a passing contract run is ``{"ok": true, "device": {...}}``; a run with
``--only`` or ``--rehearse`` can neither print that line nor exit 0.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

REPO = Path(__file__).resolve().parent
ASSET = REPO / "tests" / "assets" / "v_synth_sample.mp4"  # 355 f, 320x240
RUN = REPO / "chiprun_out" / "chip_smoke"
RESULT = RUN / "result.json"

#: the driver allows 1200 s; leave room to write the result and exit
BUDGET_S = 1100.0
#: the bar scripts/validate_kernels_tpu.py used, under matmul precision highest
KERNEL_TOL = 1e-4
#: (h8, w8, pair batch, where the system produces it)
GEOMETRIES = [
    (30, 40, 4, "raft at 240x320, this smoke's batch_size=4"),
    (28, 28, 64, "i3d flow stream at 224 px, one 64-pair stack"),
    (8, 8, 1, "raft init_params' 64x64 trace"),
    (55, 128, 1, "raft at Sintel's 436x1024, raft.yml batch_size=1"),
]
REHEARSAL_GEOMETRIES = [(8, 8, 1, "cpu rehearsal, interpreted")]

_T0 = time.monotonic()
_children: List[subprocess.Popen] = []


class StageFailed(Exception):
    pass


def check(cond: Any, msg: str) -> None:
    if not cond:
        raise StageFailed(msg)


def _kill(proc: subprocess.Popen) -> None:
    """The child leads its own session: take its decode workers with it."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()


def run_child(name: str, argv: List[str], env: Dict[str, str]) -> Path:
    """Run one child to its end; its output goes to ``{RUN}/{name}.log``.
    The exit code is checked, and trusted for nothing else."""
    left = BUDGET_S - (time.monotonic() - _T0)
    check(left > 30, f"{name}: no time left in the {BUDGET_S:.0f} s budget")
    log = RUN / f"{name}.log"
    with open(log, "wb") as out:
        proc = subprocess.Popen(argv, cwd=REPO, env=env, stdout=out,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        _children.append(proc)
        try:
            rc = proc.wait(timeout=left)
        except subprocess.TimeoutExpired:
            _kill(proc)
            raise StageFailed(f"{name}: still running when the budget ran "
                              f"out; killed (log: {log})") from None
    if rc != 0:
        tail = log.read_text(errors="replace")[-3000:]
        raise StageFailed(f"{name}: exit code {rc}; log tail:\n{tail}")
    return log


def load_json(path: Path) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise StageFailed(f"cannot read {path}: {e}") from None


def find_one(root: Path, pattern: str) -> Path:
    hits = sorted(root.rglob(pattern))
    check(len(hits) == 1, f"expected one {pattern} under {root}, found "
                          f"{[str(h) for h in hits]}")
    return hits[0]


def family_args(stage_dir: Path, platform: str) -> List[str]:
    args = [f"device={platform}", "allow_random_weights=true", "health=true",
            "telemetry=true", "on_extraction=save_numpy",
            f"output_path={stage_dir / 'out'}", f"tmp_path={stage_dir / 'tmp'}"]
    if platform == "cpu":
        # compile_cache=auto is off on the CPU backend; the rehearsal still
        # has to exercise stage 2's hit/miss reading
        args.append("compile_cache=true")
    return args


def manifest_facts(out_dir: Path, platform: str,
                   videos: int = 1) -> Dict[str, Any]:
    """What ``_run.json`` says about the device, the tally and the cache."""
    man = load_json(find_one(out_dir, "_run.json"))
    topo = man.get("topology") or {}
    check(topo.get("platform") == platform,
          f"_run.json topology.platform={topo.get('platform')!r}, expected "
          f"{platform!r}: the run did not happen on the chip")
    check(topo.get("device_kinds"), "_run.json names no device_kind")
    tally = man.get("tally") or {}
    check(tally.get("done") == videos
          and not any(n for status, n in tally.items() if status != "done"),
          f"tally {tally}, expected {videos} video(s) done and nothing else")
    cc = man.get("compile_cache") or {}
    return {"platform": topo["platform"], "device_kinds": topo["device_kinds"],
            "n_local_devices": topo.get("n_local_devices"),
            "compiled": int(cc.get("misses", 0)),
            "hits": int(cc.get("hits", 0)), "misses": int(cc.get("misses", 0)),
            "native_writer": (man.get("versions") or {}).get("native_writer")}


def load_feature(out_dir: Path, name: str):
    import numpy as np  # numpy only: the parent stays off JAX
    arr = np.load(find_one(out_dir, name))
    check(np.isfinite(arr).all(), f"{name}: non-finite values")
    return arr


# -- stages -------------------------------------------------------------------

def stage_r21d(stage_dir: Path, platform: str, env: Dict[str, str],
               warm: bool) -> Dict[str, Any]:
    run_child(stage_dir.name, [
        sys.executable, "main.py", "feature_type=r21d",
        f"video_paths=[{ASSET}]", *family_args(stage_dir, platform)], env)
    facts = manifest_facts(stage_dir / "out", platform)
    feats = load_feature(stage_dir / "out", f"{ASSET.stem}_r21d.npy")
    check(feats.shape == (22, 512),  # 355 // 16 clips
          f"r21d features {feats.shape}, expected (22, 512)")
    check(abs(feats - feats[0]).max() > 0, "r21d features constant across "
                                           "clips")
    if warm:
        check(facts["misses"] == 0 and facts["hits"] > 0,
              f"second process: {facts['hits']} hits / {facts['misses']} "
              "misses - the first process's compile cache was not found")
    return facts


def stage_serve(stage_dir: Path, platform: str,
                env: Dict[str, str]) -> Dict[str, Any]:
    from video_features_tpu import serve  # file protocol only, no JAX
    spool = stage_dir / "spool"
    videos = stage_dir / "videos"
    videos.mkdir(parents=True)
    rids = []
    for i in range(3):  # requests wait in the spool for the server to start
        copy = videos / f"smoke_req{i}.mp4"
        shutil.copyfile(ASSET, copy)
        rids.append(serve.submit_request(str(spool), [str(copy)],
                                         request_id=f"smoke-{i}"))
    run_child(stage_dir.name, [
        sys.executable, "main.py", "serve", "feature_type=r21d",
        "precision=bfloat16", f"spool_dir={spool}", "serve_max_requests=3",
        *family_args(stage_dir, platform)], env)
    facts = manifest_facts(spool, platform, videos=3)
    responses = []
    for i, rid in enumerate(rids):
        resp = serve.read_response(str(spool), rid)
        check(resp is not None, f"no done/ response for request {rid}")
        statuses = [s for per in (resp.get("videos") or {}).values()
                    for s in per.values()]
        check(resp.get("status") == "done" and statuses
              and all(s == "done" for s in statuses),
              f"request {rid}: status {resp.get('status')!r}, videos "
              f"{resp.get('videos')}")
        load_feature(stage_dir / "out", f"smoke_req{i}_r21d.npy")
        responses.append(resp)
    responses.sort(key=lambda r: r["time"])  # the order they were answered
    facts["requests"] = [
        {"id": r["id"], "latency_s": r.get("latency_s"),
         **{k: (r.get("compile_cache") or {}).get(k)
            for k in ("hits", "misses")}} for r in responses]
    for req in facts["requests"][1:]:
        check(req["misses"] == 0,
              f"request {req['id']} compiled {req['misses']} program(s): "
              "latency after request 1 must contain no compile")
    return facts


def stage_raft(stage_dir: Path, platform: str,
               env: Dict[str, str]) -> Dict[str, Any]:
    argv = [sys.executable, "main.py", "feature_type=raft",
            "extraction_total=16", "batch_size=4", f"video_paths=[{ASSET}]",
            *family_args(stage_dir, platform)]
    if platform == "cpu":
        argv.append("iters=2")  # the rehearsal checks plumbing, not RAFT
    run_child(stage_dir.name, argv, env)
    facts = manifest_facts(stage_dir / "out", platform)
    flow = load_feature(stage_dir / "out", f"{ASSET.stem}_raft.npy")
    check(flow.ndim == 4 and flow.shape[1:] == (2, 240, 320)
          and flow.shape[0] >= 12,
          f"raft flow {flow.shape}, expected (~15, 2, 240, 320)")
    facts["flow_shape"] = list(flow.shape)
    # what the traced forward said about its own lookup (models/raft.py)
    spans = find_one(stage_dir / "out", "_telemetry.jsonl")
    lookups = [ev for line in spans.read_text().splitlines() if line.strip()
               for ev in json.loads(line).get("events", [])
               if ev.get("kind") == "corr_lookup"]
    check(lookups, "no corr_lookup event in _telemetry.jsonl")
    facts["corr_lookup"] = [{k: ev.get(k) for k in
                             ("impl", "compiled", "fallback",
                              "plane_cells", "plane_fill")}
                            for ev in lookups]
    if platform == "tpu":
        check(all(ev.get("impl") == "proj"
                  and ev.get("compiled") and not ev.get("fallback")
                  for ev in lookups),
              f"raft did not run the compiled fused kernel: "
              f"{facts['corr_lookup']}")
    return facts


def stage_device_checks(stage_dir: Path, platform: str,
                        env: Dict[str, str]) -> Dict[str, Any]:
    out = stage_dir / "device_checks.json"
    run_child(stage_dir.name, [sys.executable, str(REPO / "chip_smoke.py"),
                               "--child", "device_checks", str(out)], env)
    doc = load_json(out)
    check(doc.get("platform") == platform,
          f"device checks ran on {doc.get('platform')!r}")
    if platform == "tpu":
        check(doc["raft_program"]["mosaic_custom_calls"] > 0,
              "the lowered RAFT forward holds no Mosaic custom call: the "
              "fused kernel did not lower compiled")
    bad = [k for k in doc["kernels"] if not k.get("ok")]
    check(not bad, f"kernel/geometry pairs outside {KERNEL_TOL}: "
                   f"{json.dumps(bad)}")
    return doc


# -- children that need JAX ---------------------------------------------------

def child_probe(out_path: str) -> None:
    """What JAX finds, exactly as the contract's last line reports it."""
    import jax

    from video_features_tpu.compile_cache import env_fingerprint
    devs = jax.devices()
    versions, _ = env_fingerprint()  # the runtime identity the cache keys on
    Path(out_path).write_text(json.dumps({
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
        **{k: versions[k] for k in ("jax", "jaxlib", "libtpu")}}))


def _raft_program(platform: str) -> Dict[str, Any]:
    """Lower (not run) the jitted forward ExtractRAFT builds under the
    stage-4 config and count the Mosaic custom calls in its text. Feature
    values are the same whichever lookup ran; the program text is not."""
    import jax
    import jax.numpy as jnp

    from video_features_tpu.config import load_config, sanity_check
    from video_features_tpu.extractors.raft import ExtractRAFT
    cfg = load_config("raft", {
        "device": platform, "allow_random_weights": True, "batch_size": 4,
        "extraction_total": 16, "on_extraction": "save_numpy",
        "video_paths": [str(ASSET)],
        "output_path": str(RUN / "s5" / "out"),
        "tmp_path": str(RUN / "s5" / "tmp")})
    sanity_check(cfg)
    ext = ExtractRAFT(cfg)
    batch = jax.ShapeDtypeStruct((4, 2, 240, 320, 3), jnp.uint8)
    text = ext.runner._fn.lower(ext.runner.params, batch).as_text()
    return {"batch": list(batch.shape), "precision": ext.precision,
            "mosaic_custom_calls": text.count("tpu_custom_call")}


def _kernel_check(h8: int, w8: int, pairs: int, where: str,
                  interpret: bool) -> Dict[str, Any]:
    """Both lookup kernels against their XLA twins on one /8 geometry."""
    import jax.numpy as jnp
    import numpy as np

    from video_features_tpu.kernels import corr_lookup as cl
    from video_features_tpu.models.raft import build_corr_pyramid
    rec: Dict[str, Any] = {"geometry": [h8, w8], "pairs": pairs,
                           "where": where, "interpret": interpret}
    rng = np.random.default_rng(h8 * 1000 + w8)
    f1, f2 = (jnp.asarray(rng.normal(size=(pairs, h8, w8, 256))
                          .astype(np.float32)) for _ in range(2))
    pyramid = build_corr_pyramid(f1, f2)
    # centres anywhere in the plane and up to 6 px outside it: the
    # zeros-padding rule is part of what the kernels must reproduce
    coords = jnp.asarray(rng.uniform(-6, max(h8, w8) + 6,
                                     size=(pairs, h8, w8, 2))
                         .astype(np.float32))
    weight = jnp.asarray(0.05 * rng.normal(size=(324, 256)).astype(np.float32))
    bias = jnp.asarray(rng.normal(size=(256,)).astype(np.float32))
    checks: Dict[str, Callable[[], Any]] = {
        "proj": lambda: (
            cl.corr_lookup_proj(*cl.stack_aligned_pyramid(pyramid), coords,
                                weight, bias, interpret=interpret),
            cl.corr_lookup_proj_ref(pyramid, coords, weight, bias)),
        "level": lambda: (
            cl.corr_lookup_pallas(pyramid, coords, interpret=interpret),
            cl.corr_lookup_onehot(pyramid, coords)),
    }
    # what a forward at this geometry runs on this backend: proj on the
    # chip, over a plane of so many cells a query (the shelf rule's answer)
    form = cl.prepare_lookup(pyramid)[1]
    rec["impl"] = form.impl
    rec["plane_cells"] = cl.plane_fill(form.metas)[0]
    rec["ok"] = interpret or rec["impl"] == "proj"
    for name, run in checks.items():
        try:
            got, ref = (np.asarray(x) for x in run())
            diff = float(np.max(np.abs(got - ref)))
            rec[f"{name}_max_abs"] = diff
            if not (np.isfinite(got).all() and diff <= KERNEL_TOL):
                rec["ok"] = False
        except Exception as e:  # a Mosaic rejection is this stage's finding
            rec[f"{name}_error"] = f"{type(e).__name__}: {e}"[:2000]
            rec["ok"] = False
    return rec


def child_device_checks(out_path: str) -> None:
    import jax

    from video_features_tpu import native
    platform = jax.default_backend()  # the parent holds it to what it expects
    doc: Dict[str, Any] = {"platform": platform,
                           # does g++ build the .npy writer on this machine
                           # (else the byte-identical Python path runs)
                           "native_writer_builds": native.available(),
                           "raft_program": _raft_program(platform)}
    # the extractors' float32 policy (extractors/base.py): without it the
    # MXU contracts in bf16 and the 1e-4 bar does not apply
    jax.config.update("jax_default_matmul_precision", "highest")
    geometries = GEOMETRIES if platform == "tpu" else REHEARSAL_GEOMETRIES
    doc["kernels"] = [_kernel_check(*g, interpret=platform != "tpu")
                      for g in geometries]
    Path(out_path).write_text(json.dumps(doc, indent=1))


# -- the parent ---------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--only", default="", help="comma-separated stage "
                    "numbers; such a run cannot print the success line")
    ap.add_argument("--rehearse", action="store_true", help="run the stage "
                    "logic on the CPU at tiny sizes; cannot exit 0")
    ap.add_argument("--child", nargs=2, metavar=("WHAT", "OUT"),
                    help=argparse.SUPPRESS)
    opts = ap.parse_args(argv)
    if opts.child:
        what, out = opts.child
        if what == "probe":
            child_probe(out)
        else:
            child_device_checks(out)
        return 0
    # a terminated parent still stops what it started (the finally below)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        return parent(ap, opts)
    finally:
        for proc in _children:
            _kill(proc)


def parent(ap: argparse.ArgumentParser, opts: argparse.Namespace) -> int:
    missing = [str(p.relative_to(REPO)) for p in
               (REPO / "main.py", REPO / "video_features_tpu", ASSET)
               if not p.exists()]
    if missing:
        print(f"chip_smoke: {missing} not found beside chip_smoke.py - this "
              "script drives the repository it sits in", file=sys.stderr)
        return 2
    platform = "cpu" if opts.rehearse else "tpu"
    env = dict(os.environ)
    if opts.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
    shutil.rmtree(RUN, ignore_errors=True)  # 'skipped' is not 'done'
    RUN.mkdir(parents=True)

    try:
        run_child("probe", [sys.executable, str(REPO / "chip_smoke.py"),
                            "--child", "probe", str(RUN / "probe.json")], env)
        device = load_json(RUN / "probe.json")
    except StageFailed as e:
        print(f"chip_smoke: JAX could not start: {e}", file=sys.stderr)
        return 3
    if device["platform"] != platform:
        print(f"chip_smoke: no TPU - JAX found platform "
              f"{device['platform']!r} ({device['count']} x {device['kind']}"
              f", JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). This "
              "script proves the system on the chip and has nothing to say "
              "without one.", file=sys.stderr)
        return 3
    print(f"platform={device['platform']} device_kind={device['kind']} "
          f"devices={device['count']} jax={device['jax']} "
          f"jaxlib={device['jaxlib']} libtpu={device['libtpu']}", flush=True)

    stages = [
        ("1", "r21d cold, YAML defaults",
         lambda: stage_r21d(RUN / "s1", platform, env, warm=False)),
        ("2", "r21d again, fresh process",
         lambda: stage_r21d(RUN / "s2", platform, env, warm=True)),
        ("3", "vft-serve bf16, three requests",
         lambda: stage_serve(RUN / "s3", platform, env)),
        ("4", "raft CLI, defaults at 240x320",
         lambda: stage_raft(RUN / "s4", platform, env)),
        ("5", "raft program text + kernels vs XLA twins",
         lambda: stage_device_checks(RUN / "s5", platform, env)),
    ]
    only = {s.strip() for s in opts.only.split(",") if s.strip()}
    unknown = only - {n for n, _, _ in stages}
    if unknown:
        ap.error(f"--only: no stage {sorted(unknown)}")
    records = []
    for n, title, fn in stages:
        if only and n not in only:
            continue
        (RUN / f"s{n}").mkdir(exist_ok=True)
        rec: Dict[str, Any] = {"stage": n, "title": title}
        t0 = time.monotonic()
        try:
            rec.update(fn())
            rec["ok"] = True
        except StageFailed as e:
            rec["ok"] = False
            rec["error"] = str(e)
        rec["wall_s"] = round(time.monotonic() - t0, 1)
        records.append(rec)
        print(f"stage {n} [{title}]: {'ok' if rec['ok'] else 'FAILED'} "
              f"wall={rec['wall_s']}s compiled={rec.get('compiled', '-')} "
              f"hits={rec.get('hits', '-')} misses={rec.get('misses', '-')}",
              flush=True)
        if not rec["ok"]:
            print(rec["error"], file=sys.stderr, flush=True)
    all_ok = bool(records) and all(r["ok"] for r in records)
    contract = not only and not opts.rehearse and "jax" not in sys.modules
    result = {"ok": all_ok and contract, "stages_ok": all_ok,
              "contract_run": contract, "device": device, "stages": records,
              "jax_compilation_cache_dir":
                  os.environ.get("JAX_COMPILATION_CACHE_DIR"),
              "wall_s": round(time.monotonic() - _T0, 1)}
    RESULT.write_text(json.dumps(result, indent=1))
    print(f"result: {RESULT}", flush=True)
    for rec in records:
        for k in rec.get("kernels", []):
            print(f"kernel {k['geometry']} x{k['pairs']} impl={k.get('impl')} "
                  f"plane_cells={k.get('plane_cells')}: "
                  f"proj={k.get('proj_max_abs', k.get('proj_error'))} "
                  f"level={k.get('level_max_abs', k.get('level_error'))}",
                  flush=True)
    if not all_ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    if not contract:
        print("chip_smoke: the stages run passed, but this was not the "
              "contract run (--only / --rehearse): no verdict",
              file=sys.stderr)
        return 4
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
