"""Device mesh + data-parallel batch execution.

This replaces the reference's scale-out story — "run another copy of main.py
per GPU" (reference README.md:70-84) — with in-process SPMD over a
`jax.sharding.Mesh`:

  - single host: clip/frame batches are sharded over the mesh's ``data`` axis;
    XLA partitions the jitted forward, no collectives needed (embarrassingly
    data-parallel at clip granularity, see SURVEY §2.4).
  - multi host: `jax.distributed` + deterministic video->host assignment
    (:func:`local_shard_of_list`), replacing the reference's shuffle +
    skip-if-exists collision avoidance with collision-free hashing. The
    idempotent output contract (utils/sinks.py) still makes preempted workers
    resumable.

The mesh is 1-D ("data") by default because every model family here is
data-parallel at clip granularity; a second "model" axis is reserved for
tensor-parallel experiments on the largest family (CLIP RN50x16) and for the
dryrun multichip validation path.
"""
from __future__ import annotations

import functools
import hashlib
import itertools
import threading
from pathlib import Path
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# roofline=true (telemetry/roofline.py): per-program cost-card capture at
# the dispatch boundary — one module-global read per dispatch when off
from ..telemetry.roofline import observe_dispatch as _roofline_observe
from ..telemetry import startup as _startup
from ..telemetry import trace as _trace


def get_mesh(n_devices: Optional[int] = None,
             axis_names: Tuple[str, ...] = ("data",),
             shape: Optional[Tuple[int, ...]] = None,
             backend: Optional[str] = None) -> Mesh:
    """Build a mesh over the first ``n_devices`` local devices (default: all).

    ``backend`` pins the platform (e.g. ``"cpu"``) — an explicit
    ``device=cpu`` run must never enumerate (and thereby claim) the TPU.

    Uses *addressable* devices on purpose: under ``jax.distributed`` each
    process runs its own data-parallel mesh over its own chips (extraction
    is embarrassingly parallel at clip granularity — the only multi-host
    coordination is the work-list shard, :func:`local_shard_of_list`). A
    global-device mesh here would make every ``device_put`` of host frames
    target other hosts' chips and fail. Single-process runs are unaffected
    (local == global).
    """
    devs = jax.local_devices(backend=backend) if backend \
        else jax.local_devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    if shape is None:
        shape = (len(devs),) + (1,) * (len(axis_names) - 1)
    mesh_devs = np.array(devs).reshape(shape)
    return Mesh(mesh_devs, axis_names)


def mesh_topology() -> dict:
    """JSON-safe device/mesh topology snapshot for the run manifest
    (telemetry/manifest.py): what hardware this process actually saw,
    recorded so a perf number in ``_run.json`` is interpretable months
    later. Uses the same addressable-device view as :func:`get_mesh`."""
    devs = jax.local_devices()
    kinds = sorted({getattr(d, "device_kind", "?") for d in devs})
    return {
        "platform": devs[0].platform if devs else "none",
        "device_kinds": kinds,
        "n_local_devices": len(devs),
        "n_global_devices": jax.device_count(),
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "default_mesh_axes": {"data": len(devs)},
    }


def local_shard_of_list(items: Sequence[str], host_id: Optional[int] = None,
                        num_hosts: Optional[int] = None) -> List[str]:
    """Deterministic item->host assignment: ``md5(stem) % num_hosts``.

    The multi-host analog of the reference's shuffled work list
    (reference utils/utils.py:164-165): instead of decorrelating workers
    probabilistically and tolerating duplicate work (README.md:84), each video
    is owned by exactly one host. Stable across restarts, so resume works.
    """
    if host_id is None:
        host_id = jax.process_index()
    if num_hosts is None:
        num_hosts = jax.process_count()
    if num_hosts <= 1:
        return list(items)
    out = []
    for it in items:
        # hash the stem, not the path: hosts may see the shared filesystem
        # under different mount prefixes; stems are unique (sanity_check)
        stem = Path(str(it)).stem
        h = int(hashlib.md5(stem.encode()).hexdigest(), 16)
        if h % num_hosts == host_id:
            out.append(it)
    return out


#: Megatron-style tensor-parallel rules for the transformer blocks used by
#: CLIP (models/clip.py param tree): column-parallel qkv/mlp-in (shard the
#: output feature dim + bias), row-parallel out/mlp-out (shard the input
#: dim, replicate bias — XLA inserts the psum). First match wins; everything
#: unmatched stays replicated. GSPMD propagates the internal activation
#: shardings and collectives from these param annotations alone.
TP_RULES_TRANSFORMER: Tuple[Tuple[str, int], ...] = (
    (r"attn/(q|k|v)_proj/kernel$", 1),
    (r"attn/(q|k|v)_proj/bias$", 0),
    (r"mlp_c_fc/kernel$", 1),
    (r"mlp_c_fc/bias$", 0),
    (r"attn/out_proj/kernel$", 0),
    (r"mlp_c_proj/kernel$", 0),
    # ModifiedResNet's AttentionPool2d head (the RN* checkpoints' largest
    # single weight block); the conv trunk stays replicated
    (r"attnpool/(q|k|v)_proj/kernel$", 1),
    (r"attnpool/(q|k|v)_proj/bias$", 0),
    (r"attnpool/c_proj/kernel$", 0),
)


def param_specs_by_rules(params: Any,
                         rules: Sequence[Tuple[str, int]],
                         model_axis: str = "model") -> Any:
    """PartitionSpec tree from path-regex rules: ``(pattern, dim)`` shards
    that tensor dimension over ``model_axis`` for the first matching rule;
    unmatched leaves are replicated. This is how a plain (metadata-free)
    flax param tree gets tensor-parallel layouts without rewriting modules."""
    import re

    def spec(path, x):
        p = "/".join(str(getattr(k, "key", k)) for k in path)
        for pat, dim in rules:
            if re.search(pat, p):
                s: List[Optional[str]] = [None] * np.ndim(x)
                s[dim] = model_axis
                return P(*s)
        return P()

    return jax.tree_util.tree_map_with_path(spec, params)


def settle(out: Any) -> float:
    """Completion fence via a D2H read: sums every leaf of ``out`` on host.

    A host read of the output cannot return before the output exists, and
    the device's in-order queue makes it fence every prior dispatch. Used
    by bench.py.
    """
    return float(sum(np.asarray(x).sum()
                     for x in jax.tree_util.tree_leaves(out)))


def cast_floating(tree: Any, dtype) -> Any:
    """Cast every floating-point leaf of a param tree to ``dtype``.

    This is what makes ``precision=bfloat16`` real on TPU: flax modules with
    ``dtype=None`` promote inputs and params to a common type, so a bf16
    activation against f32 params silently runs the conv/matmul in f32 on the
    MXU. Casting the params (the standard bf16-inference layout) keeps the
    whole network in bf16; norm internals still accumulate in f32
    (models/common.py BNInf rsqrt).
    """
    def cast(x):
        x = jnp.asarray(x)
        return x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x
    return jax.tree_util.tree_map(cast, tree)


def step_program_name(apply_fn: Callable) -> str:
    """``vft_<family>_<step>`` for a family's device step: the family is
    the extractor module the function (or the ``partial``'s) lives in, the
    step its name without the ``_device_`` prefix (``vft_r21d_forward_yuv420``,
    ``vft_raft_forward``). A function from outside the package (tests,
    tools) keeps its own name behind ``vft_``."""
    import re
    fn = apply_fn
    while hasattr(fn, "func"):  # functools.partial
        fn = fn.func
    where = getattr(fn, "__module__", "") or ""
    family = where.rsplit(".", 1)[-1] if "video_features_tpu" in where else ""
    name = re.sub(r"^_?(device_)?", "",
                  getattr(fn, "__name__", None) or type(fn).__name__)
    if family and not name.startswith(family):
        name = f"{family}_{name}"
    return "vft_" + re.sub(r"[^A-Za-z0-9_]+", "_", name)


class DataParallelApply:
    """Jitted, batch-sharded wrapper around ``apply_fn(params, batch)``.

    The batch's leading axis is sharded over the mesh ``data`` axis; params are
    replicated. Ragged host batches pad to a power-of-two wire bucket capped
    at ``fixed_batch`` (XLA needs static shapes — SURVEY §7 "pad+mask the
    last partial batch" — but padding on the HOST costs H2D bytes, so the
    bucket ladder bounds that waste at 2x; see ``bucket_batch_size``).
    Padded rows are dropped after device execution.
    """

    def __init__(self,
                 apply_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                 params: Any,
                 mesh: Optional[Mesh] = None,
                 data_axis: str = "data",
                 fixed_batch: Optional[int] = None,
                 param_specs: Any = None):
        self.mesh = mesh if mesh is not None else get_mesh()
        self.data_axis = data_axis
        self.fixed_batch = fixed_batch
        batch_sharding = NamedSharding(self.mesh, P(data_axis))
        if param_specs is None:
            param_shardings = NamedSharding(self.mesh, P())  # replicated
        else:
            # tensor parallelism: per-leaf PartitionSpecs (e.g. from
            # param_specs_by_rules) shard the weights over the 'model' axis;
            # GSPMD derives the activation shardings + collectives
            param_shardings = jax.tree_util.tree_map(
                lambda s: NamedSharding(self.mesh, s), param_specs,
                is_leaf=lambda x: isinstance(x, P))
        # the host side of an asynchronous copy: nothing waits for it here
        with _startup.phase("place"):
            self.params = jax.device_put(params, param_shardings)
        self._batch_sharding = batch_sharding
        #: the jitted step's stable name: a profiler trace's "XLA Modules"
        #: line says ``jit_<program>``, the host timeline's ``mesh.enqueue``
        #: carries it as ``program``. jit takes the name from the callable:
        #: an argument-less ``partial`` is a fresh object to hang it on and
        #: keeps the step's signature, so the program's parameters are named
        #: as before.
        self.program = step_program_name(apply_fn)
        step = functools.partial(apply_fn)
        step.__name__ = step.__qualname__ = self.program
        self._fn = jax.jit(
            step,
            in_shardings=(param_shardings, batch_sharding),
            out_shardings=batch_sharding,
        )
        #: dispatches of this runner, counted as they enter; the last one
        #: made by the calling thread is ``last_seq``
        self._seq = itertools.count()
        self._local = threading.local()
        #: padded shapes that have entered ``_enqueue``: the first of each
        #: traces, lowers and compiles or loads the step
        self._dispatched: set = set()

    @property
    def n_devices(self) -> int:
        return int(np.prod(self.mesh.devices.shape))

    @property
    def last_seq(self) -> Optional[int]:
        """``seq`` of the last dispatch THIS thread made (``mesh.enqueue``'s
        arg): whoever later waits for that output says so on its
        ``mesh.fetch`` span, which ties the wait to the dispatch."""
        return getattr(self._local, "seq", None)

    def _next_seq(self) -> int:
        seq = self._local.seq = next(self._seq)
        return seq

    def _enqueue(self, padded, rows: int, seq: int):
        """The one call into the jitted step, under ``mesh.enqueue``; the
        first of each padded shape under the start-up ledger's
        ``first_dispatch`` too (two threads that meet on a new shape both
        record it: both waited)."""
        if padded.shape not in self._dispatched:
            self._dispatched.add(padded.shape)
            with _startup.phase("first_dispatch", program=self.program,
                                padded_rows=int(padded.shape[0])):
                return self._enqueue(padded, rows, seq)
        with _trace.span("mesh.enqueue", seq=seq, rows=rows,
                         padded_rows=int(padded.shape[0]),
                         program=self.program):
            return self._fn(self.params, padded)

    def padded_batch_size(self, batch_size: int) -> int:
        """Smallest multiple of the *data-axis* size >= batch_size (on a 2-D
        (data, model) mesh the batch only splits over 'data'; padding to the
        total device count would over-pad by the model-parallel factor)."""
        n = int(self.mesh.shape[self.data_axis])
        return ((batch_size + n - 1) // n) * n

    def bucket_batch_size(self, n: int) -> int:
        """Wire-efficient static shape for a ragged HOST batch: the smallest
        mesh-divisible power-of-two step >= n, capped at ``fixed_batch``.

        Padding ragged groups all the way to ``fixed_batch`` on the host
        ships up to fixed_batch/n more H2D bytes than the rows need — at
        B=128 the 22-clip sample video paid a 5.8x wire tax per flush.
        Bucketing bounds the padding waste at 2x while
        keeping the executable count logarithmic (each bucket size compiles
        once and lands in the persistent cache)."""
        b = self.padded_batch_size(max(n, 1))
        t = self.padded_batch_size(1)
        while t < b:
            t *= 2  # stays mesh-divisible: n_data * 2^k
        if self.fixed_batch is not None:
            full = self.padded_batch_size(self.fixed_batch)
            if t >= full:
                t = full
        # never below the rows actually present (oversized host batches —
        # n > fixed_batch — must pad up like before, not truncate the pad)
        return max(t, b)

    def _pad(self, batch_np: np.ndarray) -> np.ndarray:
        """Pad a host batch to its wire bucket (``bucket_batch_size``), or a
        chained device batch up to ``fixed_batch`` — device padding is free
        and keeping the one fixed shape avoids retracing the consumer.
        Device arrays (e.g. the i3d flow->i3d handoff) pad with jnp —
        async, on device — so a ragged group never forces a D2H round trip
        of the intermediate."""
        is_device = isinstance(batch_np, jax.Array)
        if is_device or self.fixed_batch is None:
            target = max(batch_np.shape[0], self.fixed_batch or 0)
            full = self.padded_batch_size(target)
        else:
            full = self.bucket_batch_size(batch_np.shape[0])
        if full != batch_np.shape[0]:
            pad_width = [(0, full - batch_np.shape[0])] + \
                        [(0, 0)] * (batch_np.ndim - 1)
            xp = jnp if isinstance(batch_np, jax.Array) else np
            batch_np = xp.pad(batch_np, pad_width)
        if isinstance(batch_np, jax.Array):
            # chained-runner inputs carry the *producer's* sharding; the jit
            # below requires the batch sharding exactly, so reshard on device
            # (async; a no-op when shardings already match)
            batch_np = jax.device_put(batch_np, self._batch_sharding)
        return batch_np

    def dispatch(self, batch_np: np.ndarray) -> jnp.ndarray:
        """Pad + enqueue the jitted forward; returns the device array
        WITHOUT synchronizing (JAX dispatch is async — the host thread is
        free as soon as the computation is enqueued). Padded rows are NOT
        dropped; callers track validity (see :class:`FeatureStream`).

        Host batches go through an explicit ``device_put`` under an
        ``h2d`` profiler stage, so the per-stage breakdown (profile=true,
        trace=true, scripts/throughput.py --stages) can attribute wire
        time separately from decode/transform and device compute. The put
        is what the jit's implicit transfer would have done anyway — on
        accelerators the DMA completes asynchronously, so the stage times
        the host-side staging copy + enqueue (a lower bound on wire
        time); on CPU it is the full copy."""
        rows, seq = int(batch_np.shape[0]), self._next_seq()
        with _trace.span("mesh.pad", seq=seq, rows=rows):
            padded = self._pad(batch_np)
        _roofline_observe(self, padded)
        if not isinstance(padded, jax.Array):
            from ..utils.profiling import profiler
            with profiler.stage("h2d"):
                padded = jax.device_put(padded, self._batch_sharding)
        return self._enqueue(padded, rows, seq)

    def __call__(self, batch_np: np.ndarray, n_valid: Optional[int] = None
                 ) -> np.ndarray:
        """Run a (possibly ragged) batch; returns only the valid rows."""
        from ..utils.profiling import profiler
        n = batch_np.shape[0] if n_valid is None else n_valid
        rows, seq = int(batch_np.shape[0]), self._next_seq()
        with _trace.span("mesh.pad", seq=seq, rows=rows):
            padded = self._pad(batch_np)  # host copy kept out of the stage
        _roofline_observe(self, padded)
        # np.asarray blocks on the device->host copy, so this stage is true
        # H2D + forward + D2H wall time
        with profiler.stage("forward"):
            out = self._enqueue(padded, rows, seq)
            with _trace.span("mesh.fetch", seq=seq):
                return np.asarray(out)[:n]

    def stream(self, depth: int = 4,
               callback: Optional[Callable[[np.ndarray, Any], None]] = None
               ) -> "FeatureStream":
        return FeatureStream(self, depth=depth, callback=callback)


class FeatureStream:
    """Ordered async pipeline over a :class:`DataParallelApply`.

    The synchronous ``runner(batch)`` call blocks on the device->host copy of
    every batch, serializing host work with the device. ``submit`` instead just
    enqueues the jitted forward — decode of batch k+1, device compute of
    batch k, and the D2H of batch k-``depth`` all overlap — and ``finish``
    materializes every result in submit order.

    ``depth`` bounds how many un-materialized outputs may live on the device
    at once — exactly: the oldest output is drained *before* a new batch is
    dispatched when at capacity (matters for flow families, whose per-batch
    output is a full (B, H, W, 2) field). 0 means synchronous: each submit
    materializes its result before returning.

    ``callback(feats, ctx)`` (optional) fires at materialization time, in
    submit order, with the valid rows and the ``ctx`` passed to ``submit`` —
    how show_pred paths get per-batch host values (with depth=0 to keep the
    reference's print-as-you-go behavior) without a second code path in the
    extractors.
    """

    def __init__(self, runner: Optional[DataParallelApply], depth: int = 4,
                 callback: Optional[Callable[[np.ndarray, Any], None]] = None):
        from collections import deque
        self.runner = runner
        self.depth = max(int(depth), 0)
        self.callback = callback
        self._inflight: Any = deque()  # (device_array, n_valid, ctx, seq)
        self._done: List[np.ndarray] = []

    def submit(self, batch_np: np.ndarray, n_valid: Optional[int] = None,
               ctx: Any = None) -> None:
        n = batch_np.shape[0] if n_valid is None else n_valid
        while self._inflight and len(self._inflight) >= self.depth:
            self._pop()  # drain BEFORE dispatching: bound holds during _pop
        dev = self.runner.dispatch(batch_np)
        self.submit_device(dev, n, ctx,
                           seq=getattr(self.runner, "last_seq", None))

    def submit_device(self, dev: jnp.ndarray, n_valid: int,
                      ctx: Any = None, seq: Optional[int] = None) -> None:
        """Enqueue an ALREADY-dispatched device array (multi-runner
        pipelines, e.g. i3d's per-stream chains, dispatch themselves); the
        stream still bounds retained results and materializes in order. A
        runner-less stream (``FeatureStream(None, ...)``) supports only this
        entry point."""
        if self.callback is None:
            ctx = None  # don't pin (possibly large) host batches in the queue
        while self._inflight and len(self._inflight) >= max(self.depth, 1):
            self._pop()
        self._inflight.append((dev, n_valid, ctx, seq))
        _trace.counter("stream.inflight", len(self._inflight))
        if self.depth == 0:
            self._pop()

    def _pop(self) -> None:
        from ..utils.profiling import profiler
        out, n, ctx, seq = self._inflight.popleft()
        # the blocking host copy: under the profiler this stage is the
        # pipeline's *stall* time on the device, not raw device time — by
        # design everything else already happened in the background
        with profiler.stage("forward"), _trace.span("mesh.fetch", seq=seq):
            feats = np.asarray(out)[:n]
        if self.callback is not None:
            self.callback(feats, ctx)
        self._done.append(feats)

    def finish(self) -> List[np.ndarray]:
        """Materialize all pending results; returns them in submit order."""
        while self._inflight:
            self._pop()
        done, self._done = self._done, []
        return done
