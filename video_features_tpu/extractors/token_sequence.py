"""What the token-sequence extractors share (``granite_hybrid``,
``deepseek_v2``, ``lfm2_moe``, ``nemotron_h``).

The item is a file of token ids (``.tokens``: raw little-endian int32, what
a tokenizer run over a caption or a transcript leaves), not a video. A
document is cut into windows of ``stack_size`` tokens every ``step_size``, as
a video is cut into clip stacks, but the last, shorter window is kept: text
has no frame to drop. Every window is one segment of a packed row
(``parallel/packer.py SegmentPacker``: documents of all the workers share
rows and groups), and comes back as one line: the mean of the final hidden
states over its tokens (``{stem}_{feature_type}.npy``, ``(windows, hidden)``
float32) and, beside it as ``fps`` rides beside other families' features, how
many of its tokens each routed layer's router sent to each expert
(``{stem}_expert_tokens.npy``, ``(windows, routed layers, experts)`` int32).

No checkpoint converter exists yet: ``allow_random_weights=true`` draws the
model's seeded weights on the device, in the serving type, layer by layer
(``weights/store.py``'s random path builds a float32 tree on the host: 19 GB
for granite's benchmark configuration).

A family is a subclass that names its model module (``arch_from_config``,
``init_params``; an ``Arch`` with ``vocab_held``, ``first_expert``,
``experts_held``, ``feature_dim``, ``counter_shape``, ``counter_dim``), its
device step (a function of its own file: the jitted program is named after
it, ``parallel/mesh.py step_program_name``) and its default window.
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import telemetry
from ..config import Config
from ..kernels import grouped_matmul
from ..ops import moe
from ..parallel.mesh import DataParallelApply
from ..parallel.packer import SegmentPacker
from ..parallel.sequence import stated_tile
from ..telemetry import startup, trace
from ..utils.profiling import profiler
from .base import BaseExtractor

#: a token item's suffix; the sink's stem rule takes any
TOKEN_SUFFIX = ".tokens"
#: the seed of ``allow_random_weights``
WEIGHTS_SEED = 0


def read_tokens(path: str, vocab_held: int, family: str) -> np.ndarray:
    """The ids of a ``.tokens`` file. An id outside the rows of the
    vocabulary this chip holds is refused here, where the item is read: on
    the device it would be clamped to another token's row in silence."""
    if not str(path).endswith(TOKEN_SUFFIX):
        raise NotImplementedError(
            f"{family} reads {TOKEN_SUFFIX} files (raw int32 token ids), "
            f"got {path!r}")
    ids = np.fromfile(path, dtype="<i4")
    if ids.size and not (0 <= int(ids.min()) and int(ids.max()) < vocab_held):
        raise ValueError(
            f"{path}: token ids {int(ids.min())}..{int(ids.max())} outside "
            f"the {vocab_held} vocabulary rows held here")
    return ids


def windows_of(n: int, window: int, step: int):
    """``[(start, end)]`` of a document of ``n`` tokens: the last window is
    as short as the document leaves it (``ceil(n / window)`` windows where
    ``step == window``); an empty document has none."""
    if n <= 0:
        return []
    count = 1 if n <= window else -(-(n - window) // step) + 1
    return [(i * step, min(i * step + window, n)) for i in range(count)]


class TokenSequenceExtractor(BaseExtractor):
    #: the family's module under ``models/``
    model = None
    #: ``device_forward(arch, max_segments, dtype, params, rows)``: (B, 2, T)
    #: int32 ids and segment ids -> (B, max_segments, hidden + routed layers
    #: * experts) float32
    device_forward = None
    #: ``stack_size: null`` resolves to this many tokens
    default_stack_size = 4096

    _moe_stated = False

    def __init__(self, args: Config) -> None:
        super().__init__(args)
        self.model_name = args.get("model_name")
        self.arch = self.model.arch_from_config(
            dict(args.architecture), args.get("layer_shards") or 1,
            args.get("layer_shard_rank") or 0)
        self.stack_size = int(args.get("stack_size")
                              or self.default_stack_size)
        self.step_size = int(args.get("step_size") or self.stack_size)
        self.batch_size = int(args.get("batch_size") or 4)
        self.max_segments = int(args.get("max_segments") or 64)
        self.output_feat_keys = [self.feature_type, "expert_tokens"]
        if args.get("weights_path") or not args.get("allow_random_weights"):
            raise NotImplementedError(
                f"{self.feature_type} has no checkpoint converter yet: pass "
                "allow_random_weights=true (seeded weights, made on the "
                "device) and no weights_path")
        self.dtype = jnp.bfloat16 if self.precision == "bfloat16" \
            else jnp.float32
        mesh = self._data_mesh()
        # drawn where they will live: a second copy would not fit
        params = self.model.init_params(self.arch, WEIGHTS_SEED, self.dtype,
                                        sharding=NamedSharding(mesh, P()))
        # how the routed layers' grouped products will run, from the gate
        # the step itself asks as it is traced (``ops/moe.py``)
        routed = next(w for w in params["layers"] if "experts_in" in w)
        self._moe_products = moe.stated_products(
            self.batch_size * self.stack_size, self.arch.num_experts_per_tok,
            self.arch.counter_shape[-1], self.dtype, routed["experts_in"],
            routed["experts_out"])
        if self._moe_products["products"] == "pallas":
            # Pallas's import is a second of this start: named here, and
            # not spent inside the step's first trace
            with startup.phase("kernels"):
                grouped_matmul.ready()
        self.runner = DataParallelApply(
            partial(type(self).device_forward, self.arch, self.max_segments,
                    self.dtype),
            params, mesh=mesh, fixed_batch=self.batch_size)
        self._packer = SegmentPacker(self.runner, batch=self.batch_size,
                                     row_len=self.stack_size,
                                     max_segments=self.max_segments)

    def extract(self, video_path: str) -> Dict[str, np.ndarray]:
        if not self._moe_stated:
            # feature values do not say which form the routed experts'
            # grouped products ran in: a ``moe`` event on the first item's
            # span does (telemetry=true)
            self._moe_stated = True
            telemetry.event("moe", **self._moe_products)
            # nor the tile attention's masked loop scores a step at: an
            # ``attention`` event does
            telemetry.event("attention", **stated_tile(
                self.arch.num_attention_heads, self.stack_size))
        with profiler.stage("decode"), \
                trace.span("decode.read", item=str(video_path)):
            ids = read_tokens(video_path, self.arch.vocab_held,
                              self.feature_type)
        windows = windows_of(len(ids), self.stack_size, self.step_size)
        handle = self._packer.open_video()
        try:
            for start, end in windows:
                self._packer.add(handle, ids[start:end])
        except BaseException:
            self._packer.abort_video(handle)
            raise
        lines = self._packer.close_video(handle).reshape(
            len(windows), self.arch.feature_dim + self.arch.counter_dim)
        counts = np.rint(lines[:, self.arch.feature_dim:]).astype(
            np.int32).reshape(len(windows), *self.arch.counter_shape)
        if len(windows):
            # how near this document's routing runs to the routed layer's
            # compact buffer (``ops/moe.py held_rows``): the assignments of
            # its fullest layer to the experts held here, beside all of one
            # layer's
            a_layer = counts.sum(axis=0)         # (routed layers, experts)
            first = self.arch.first_expert
            held = a_layer[:, first:first + self.arch.experts_held].sum(
                axis=1)
            trace.counter("moe.assignments", int(held.max()), series="held")
            trace.counter("moe.assignments", int(a_layer[0].sum()),
                          series="all")
            # how uneven each routed layer's load is: its fullest expert
            # over the mean of the router's width; and what share of the
            # layer's assignments the experts held here take
            for i, load in enumerate(a_layer):
                trace.counter("moe.fullest_over_mean",
                              float(load.max() / load.mean()),
                              series=f"layer{i}")
                trace.counter("moe.held_share",
                              float(held[i] / max(load.sum(), 1)),
                              series=f"layer{i}")
        if self.show_pred:
            self.maybe_show_pred(ids, windows)
        return {self.feature_type: np.ascontiguousarray(
                    lines[:, :self.arch.feature_dim], np.float32),
                "expert_tokens": counts}

    def maybe_show_pred(self, ids: np.ndarray, windows) -> None:
        raise NotImplementedError(
            f"{self.feature_type} holds no output head: show_pred=false")
