"""Microbenchmark: pallas vs XLA for the hot kernels, on the real chip.

Runs on a TPU only: off one, corr_lookup_pallas would be the Pallas
interpreter, and a time for it says nothing. Used to pick dispatch defaults;
results recorded in the kernels package docstrings.
"""
import sys
import time
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from video_features_tpu.kernels.corr_lookup import (corr_lookup_onehot,
                                                    corr_lookup_pallas)
from video_features_tpu.models.raft import (build_corr_pyramid,
                                            corr_lookup_gather)


def timeit(fn, *args, iters=200):
    # D2H-fenced (parallel/mesh.py settle)
    from video_features_tpu.parallel.mesh import settle
    settle(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    settle(out)
    return (time.perf_counter() - t0) / iters * 1e3  # ms


def main():
    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"bench_kernels: backend {jax.default_backend()!r} "
            f"({jax.devices()}) is not a TPU; nothing here is worth timing "
            "off the chip")
    print("platform:", jax.devices()[0])
    rng = np.random.default_rng(0)

    print("\n-- RAFT corr lookup (B, H8, W8) --")
    for b, h8, w8 in [(1, 46, 46), (4, 46, 46), (8, 28, 28)]:
        c = 256
        f1 = jnp.asarray(rng.normal(size=(b, h8, w8, c)).astype(np.float32))
        f2 = jnp.asarray(rng.normal(size=(b, h8, w8, c)).astype(np.float32))
        pyramid = jax.block_until_ready(build_corr_pyramid(f1, f2))
        coords = jnp.asarray(
            rng.uniform(0, h8, size=(b, h8, w8, 2)).astype(np.float32))
        gather_fn = jax.jit(corr_lookup_gather)
        onehot_fn = jax.jit(corr_lookup_onehot)
        pallas_fn = jax.jit(corr_lookup_pallas)  # one jit: no per-level dispatch
        t_g = timeit(gather_fn, pyramid, coords)
        t_o = timeit(onehot_fn, pyramid, coords)
        t_p = timeit(pallas_fn, pyramid, coords)
        print(f"B={b} {h8}x{w8}: gather {t_g:.3f} ms  onehot {t_o:.3f} ms  "
              f"pallas {t_p:.3f} ms")


if __name__ == "__main__":
    main()
