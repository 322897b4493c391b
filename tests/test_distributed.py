"""Real two-process ``jax.distributed`` extraction (the only scale-out branch
tests could not cover in-unit).

Two actual OS processes connect to one coordinator, run the REAL CLI with
``distributed=true`` into ONE shared output directory, and exit. Asserts:

  - both processes see ``process_count() == 2`` (the distributed runtime
    actually formed, not two independent singletons);
  - the work list is split disjointly and completely: every video's features
    exist exactly once in the shared dir, and each worker's runtime-derived
    shard (``local_shard_of_list`` under the real ``jax.process_index()``)
    matches the deterministic expectation computed in-test;
  - each worker's own shard was fully written before it exited;
  - clean exits (rc 0), no output corruption (files load).

The CLI's distributed branch (cli.py: jax.distributed.initialize before any
backend touch) is entered by both workers; the test driver pre-initializes
with explicit coordinator/process args — the branch's already-initialized
guard must then no-op instead of raising.

Subprocess logs go to files, never PIPEs (an un-drained PIPE once deadlocked
a SIGTERM test on this host — see tests/test_multihost.py).
"""
import os
import socket
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from video_features_tpu.parallel.mesh import local_shard_of_list

N_VIDEOS = 6
TIMEOUT_S = 480


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


_WORKER = textwrap.dedent("""
    import sys
    from pathlib import Path
    sys.path.insert(0, {repo!r})
    import jax
    # a 2-process probe must not claim a chip on a TPU host
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address={coord!r},
                               num_processes=2, process_id={pid})
    assert jax.process_count() == 2, jax.process_count()
    from video_features_tpu.cli import main
    main([
        "feature_type=resnet", "model_name=resnet18", "device=cpu",
        "distributed=true", "allow_random_weights=true", "batch_size=16",
        "extraction_fps=2", "on_extraction=save_numpy",
        "output_path={out}", "tmp_path={tmp}",
        "file_with_video_paths={listfile}",
    ])
    # report the shard the real runtime (process_index) assigned this worker,
    # and require its own outputs to already exist at exit
    from video_features_tpu.parallel.mesh import local_shard_of_list
    videos = Path({listfile!r}).read_text().split()
    mine = local_shard_of_list(videos)
    feat_dir = Path({out!r}) / "resnet" / "resnet18"
    for v in mine:
        f = feat_dir / (Path(v).stem + "_resnet.npy")
        assert f.exists(), f
    print("SHARD", {pid}, ",".join(sorted(Path(v).stem for v in mine)))
    print("WORKER_DONE", {pid}, jax.process_count())
""")


def test_two_process_distributed_extraction(sample_video, tmp_path):
    videos = []
    for i in range(N_VIDEOS):
        dst = tmp_path / f"v_dist_{i:03d}.mp4"
        dst.write_bytes(Path(sample_video).read_bytes())
        videos.append(str(dst))
    listfile = tmp_path / "videos.txt"
    listfile.write_text("\n".join(videos) + "\n")

    # expected deterministic split (the exact hashing the workers run)
    shards = [local_shard_of_list(videos, host_id=i, num_hosts=2)
              for i in range(2)]
    assert sorted(shards[0] + shards[1]) == sorted(videos)
    assert not (set(shards[0]) & set(shards[1]))
    # the fixed stem names make both shards non-empty; if this ever trips,
    # rename the copies rather than weakening the assert
    assert shards[0] and shards[1]

    out = tmp_path / "out"
    coord = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    procs, logs = [], []
    for pid in range(2):
        script = _WORKER.format(
            repo=str(Path(__file__).resolve().parent.parent),
            coord=coord, pid=pid, out=str(out),
            tmp=str(tmp_path / f"wtmp_{pid}"), listfile=str(listfile))
        log = open(tmp_path / f"worker_{pid}.log", "w")
        logs.append(log)
        procs.append(subprocess.Popen([sys.executable, "-c", script],
                                      stdout=log, stderr=subprocess.STDOUT,
                                      env=env))
    try:
        for p in procs:
            assert p.wait(timeout=TIMEOUT_S) == 0, _tail(tmp_path)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for log in logs:
            log.close()

    # every video extracted exactly once into the shared dir, loadable
    feat_dir = out / "resnet" / "resnet18"
    for v in videos:
        stem = Path(v).stem
        f = feat_dir / f"{stem}_resnet.npy"
        assert f.exists(), f"missing features for {stem}: {_tail(tmp_path)}"
        arr = np.load(f)  # corruption check: must load
        assert arr.ndim == 2 and arr.shape[1] == 512

    # runtime-derived shards match the deterministic expectation
    for pid in range(2):
        logtext = (tmp_path / f"worker_{pid}.log").read_text()
        assert f"WORKER_DONE {pid} 2" in logtext, logtext[-2000:]
        want = ",".join(sorted(Path(v).stem for v in shards[pid]))
        assert f"SHARD {pid} {want}" in logtext, (want, logtext[-2000:])


def _tail(tmp_path):
    return "\n".join(
        f"--- worker {i} ---\n" +
        (tmp_path / f"worker_{i}.log").read_text()[-1500:]
        for i in range(2))
