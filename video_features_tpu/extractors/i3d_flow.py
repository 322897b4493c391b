"""I3D flow stream: RAFT/PWC flow -> flow-quantization transforms -> I3D.

Composes the flow models into ExtractI3D, mirroring reference
models/i3d/extract_i3d.py:140-169:

  - flow is computed between consecutive frames of the resized, *uncropped*
    stack; the RAFT path replicate-pads the whole stack to /8 first
    (``padder.pad(rgb_stack)[:-1]`` vs ``[1:]``, extract_i3d.py:153) and the
    flow is never unpadded,
  - so the flow transform chain TensorCenterCrop(224) -> Clamp(-20, 20) ->
    ToUInt8 -> ScaleTo1_1 (extract_i3d.py:53-59) crops the center of the
    *padded* flow field,
  - the quantized flow feeds the 2-channel I3D (Kinetics flow checkpoint).

TPU split of that chain: RAFT + crop + clamp + quantization run in one jitted
pair-batched program (the D2H transfer is the small (T, 224, 224, 2) crop,
not the full-resolution field); the [-1, 1] scaling runs inside the jitted
I3D forward where XLA fuses it into the first conv. ``ToUInt8`` is
``round(128 + 255/40 * x)`` on *floats* — values can reach 256.0 at the +20
clamp boundary and torch's round is half-to-even, matching ``jnp.round`` —
so the intermediate stays float32 rather than an actual uint8 cast
(reference models/transforms.py:168-176). The PWC path (extract_i3d.py:
154-155) skips the padder: PWCNet handles sizing internally.
"""
from __future__ import annotations

from functools import partial

import jax.numpy as jnp
import numpy as np

from ..models import i3d as i3d_model
from ..models import raft as raft_model
from ..parallel.mesh import DataParallelApply, cast_floating
from ..weights import store


def _crop_quantize(flow: jnp.ndarray, crop: int) -> jnp.ndarray:
    """TensorCenterCrop -> Clamp(-20,20) -> ToUInt8 (extract_i3d.py:53-59)."""
    hp, wp = flow.shape[1], flow.shape[2]
    i, j = (hp - crop) // 2, (wp - crop) // 2  # TensorCenterCrop floor rule
    flow = flow[:, i:i + crop, j:j + crop, :]
    flow = jnp.clip(flow, -20.0, 20.0)
    return jnp.round(128.0 + 255.0 / 40.0 * flow)


def _raft_quantized_flow(model: raft_model.RAFT, crop: int, params,
                         pairs_u8):
    """(B, 2, H, W, 3) uint8 -> (B, crop, crop, 2) quantized flow floats."""
    flow, _ = raft_model.padded_flow(model, params,
                                     pairs_u8.astype(jnp.float32))
    return _crop_quantize(flow, crop)


def _pwc_quantized_flow(model, crop: int, params, pairs_u8):
    """PWC twin of :func:`_raft_quantized_flow` — input-resolution flow, no
    padding (the crop happens on the unpadded field)."""
    x = pairs_u8.astype(jnp.float32)
    flow = model.apply({"params": params}, x[:, 0], x[:, 1])
    return _crop_quantize(flow, crop)


#: Share of device memory one pair-batch forward's correlation pyramid may
#: take — the dominant RAFT allocation, (pairs, P, rows, lanes) f32
#: (kernels/corr_lookup stack_aligned_pyramid). 7/16 of a 16 GB v5e picks 4
#: stacks/forward at the 224px flagship geometry (3.3 GB since the levels
#: share one 32 x 128 shelf; 6.6 GB measured fine incl. towers before) and
#: scales down for larger source resolutions.
_FLOW_PYRAMID_SHARE = 7 / 16

#: what the CPU backend (tests, device=cpu), which reports no memory
#: stats, sizes against instead: the share of a 16 GiB device
_FLOW_PYRAMID_BUDGET_CPU = int(_FLOW_PYRAMID_SHARE * 16 * 1024 ** 3)


def _flow_pyramid_budget(device) -> int:
    """The pyramid budget in bytes for ``device`` (the first device of
    the extractor's mesh — every device of a mesh is the same kind). On a
    TPU a missing ``bytes_limit`` is an error, not an assumed 16 GB chip:
    a smaller-HBM chip would OOM at the k the constant picks, a larger one
    under-batch."""
    limit = (device.memory_stats() or {}).get("bytes_limit")
    if limit:
        return int(limit * _FLOW_PYRAMID_SHARE)
    if device.platform == "tpu":
        raise RuntimeError(
            f"{device} reports no memory_stats()['bytes_limit']: cannot "
            "size flow_stack_batch=auto against its HBM. Pass "
            "flow_stack_batch=<int>.")
    return _FLOW_PYRAMID_BUDGET_CPU


def _stacks_per_forward(t: int, h: int, w: int, budget: int,
                        cap: int = 4) -> int:
    """How many stacks' pair batches to fuse into one flow forward.

    Round-4 measurement (interleaved): 1 ->
    2 -> 4 stacks per RAFT forward measured 3.94 -> 4.41 -> 4.50 stacks/s
    unfused and 5.90 -> 6.34 fused at 64f@224px on v5e — more queries per
    launch amortize per-dispatch and per-scan-iteration fixed costs.
    Power-of-two result (wire buckets pad power-of-two), capped by the
    pyramid HBM ``budget`` (:func:`_flow_pyramid_budget`) at this
    geometry."""
    from ..kernels.corr_lookup import stacked_plane_cells
    h8, w8 = -(-h // 8), -(-w // 8)  # RAFT pads inputs to /8 (InputPadder)
    per_stack = t * (h8 * w8) * 4 * stacked_plane_cells(
        h8, w8, levels=raft_model.CORR_LEVELS)
    k = 1
    while k * 2 <= cap and (k * 2) * per_stack <= budget:
        k *= 2
    return k


def _pwc_stacks_per_forward(t: int, h: int, w: int, budget: int,
                            cap: int = 4, bytes_per_el: int = 2) -> int:
    """PWC twin of :func:`_stacks_per_forward`.

    PWC's dominant live set is not an all-pairs pyramid but the per-pair
    decoder activations: two extractor pyramids (~15·HpWp elements/pair
    summed over levels) plus the /4-resolution DenseNet concat stack
    (peak ~565 channels -> ~35·HpWp) and smaller coarse levels (~20·HpWp),
    ≈ 70·Hp·Wp elements/pair — ~9 MB/pair bf16 at 256x256 (validated:
    256 pairs = 2.3 GB ran clean on v5e in the round-5 A/B).
    ``bytes_per_el`` is 2 under precision=bfloat16, 4 for f32 runs (the
    default precision) — the caller passes the flow dtype's width.
    Power-of-two k under the device-derived budget, same wire-bucket
    rationale."""
    hp, wp = -(-h // 64) * 64, -(-w // 64) * 64
    per_pair = 70 * hp * wp * bytes_per_el
    per_stack = t * per_pair
    k = 1
    while k * 2 <= cap and (k * 2) * per_stack <= budget:
        k *= 2
    return k


class FlowStream:

    def __init__(self, parent, args, mesh, dtype, allow_random) -> None:
        self.parent = parent
        self._flow_dtype = dtype  # sizes the PWC stack-batch HBM budget
        self._budget = _flow_pyramid_budget(mesh.devices.flat[0])
        # stacks fused per flow forward: 'auto' (geometry-sized at dispatch,
        # see _stacks_per_forward) or a forced integer
        raw_sb = args.get("flow_stack_batch", "auto")
        self.stack_batch = None if raw_sb in (None, "auto") else int(raw_sb)
        if self.stack_batch is not None and self.stack_batch < 1:
            raise ValueError(
                f"flow_stack_batch={self.stack_batch}: need >= 1 or 'auto'")
        crop = parent.central_crop_size
        if parent.flow_type == "raft":
            # the reference hardcodes the sintel checkpoint for the i3d flow
            # sub-model (extract_i3d.py:178); flow_iters trades flow accuracy
            # for speed (fewer GRU refinement steps) — default is the
            # reference's fixed 20 (raft.py:118). Under precision=bfloat16
            # the RAFT conv stacks run bf16 too (models/raft.py RAFT.dtype):
            # the ~0.1 px flow drift is well under the ToUInt8 quantization
            # step this stream applies anyway. The standalone RAFT extractor
            # stays f32 — there the flow field IS the output.
            raw = args.get("flow_iters")
            iters = raft_model.ITERS if raw is None else int(raw)
            if iters < 1:
                raise ValueError(
                    f"flow_iters={iters}: RAFT needs at least one GRU "
                    "refinement iteration")
            flow_model = raft_model.RAFT(iters=iters, dtype=dtype)
            flow_params = store.resolve_params(
                "raft_sintel", raft_model.init_params,
                raft_model.params_from_torch,
                weights_path=args.get("flow_model_weights_path"),
                allow_random=allow_random)
            flow_params = cast_floating(flow_params, dtype)
            self._quant_fn = partial(_raft_quantized_flow, flow_model, crop)
            self.pair_runner = DataParallelApply(
                self._quant_fn, flow_params,
                mesh=mesh, fixed_batch=parent.stack_size)
        elif parent.flow_type == "pwc":
            # PWC path: no padder — the net resizes to /64 internally and
            # returns input-resolution flow (extract_i3d.py:154-155).
            # Under precision=bfloat16 the conv stacks run bf16 like RAFT's
            # (models/pwc.py PWCNet.dtype; flow/warp math stays f32):
            # measured drift 0.015 px max — an order of magnitude under
            # the ToUInt8 quantization step this stream applies.
            from ..models import pwc as pwc_model
            flow_model = pwc_model.PWCNet(dtype=dtype)
            flow_params = store.resolve_params(
                "pwc_sintel", pwc_model.init_params,
                pwc_model.params_from_torch,
                weights_path=args.get("flow_model_weights_path"),
                allow_random=allow_random)
            self._quant_fn = partial(_pwc_quantized_flow, flow_model, crop)
            self.pair_runner = DataParallelApply(
                self._quant_fn, flow_params,
                mesh=mesh, fixed_batch=parent.stack_size)
        else:
            raise NotImplementedError(
                f"flow_type={parent.flow_type!r}; reference supports "
                "raft/pwc (extract_i3d.py:151-157)")

        from .i3d import _i3d_forward
        i3d_params = store.resolve_params(
            "i3d_flow", partial(i3d_model.init_params, "flow"),
            i3d_model.params_from_torch,
            weights_path=args.get("flow_weights_path"),
            allow_random=allow_random)
        # cast once for both runners
        i3d_params = cast_floating(i3d_params, dtype)
        self.runner = DataParallelApply(
            partial(_i3d_forward, parent.model, dtype, True),
            i3d_params, mesh=mesh, fixed_batch=parent.clip_batch_size)
        if parent.show_pred:
            parent.logits_runners["flow"] = DataParallelApply(
                partial(_i3d_forward, parent.model, dtype, False),
                i3d_params, mesh=mesh, fixed_batch=parent.clip_batch_size)

    def run(self, group: np.ndarray, stack_base: int) -> np.ndarray:
        """group: (G, stack+1, H, W, 3) uint8 resized frames -> (G, 1024).

        The flow->i3d handoff stays on device: each stack's pair batch is
        *dispatched* (async, no D2H) and the quantized crops — the largest
        intermediate, (G, T, 224, 224, 2) float32 — are stacked as device
        arrays and fed straight to the I3D runner. Only the (G, 1024)
        features cross back to the host (the reference round-trips every
        stack through host tensors between its two models)."""
        flow_in = self._device_flow(group)
        out = self.runner(flow_in)
        self.parent.maybe_show_pred("flow", flow_in, stack_base)
        return out

    def dispatch(self, group: np.ndarray):
        """Async twin of :meth:`run` (no show_pred): the whole flow->i3d
        chain enqueued, un-materialized (G_padded, 1024) device array out."""
        return self.runner.dispatch(self._device_flow(group))

    def dispatch_resized(self, resized_u8):
        """resize=device path: same chain but over the already-on-device
        resized (G, T+1, oh, ow, 3) uint8 group — pairs are formed by lazy
        device slices, so nothing extra crosses H2D and no frame is resized
        twice. The base pair runner works unchanged (it accepts uint8/float
        frames at the resized geometry)."""
        return self.runner.dispatch(self._device_flow(resized_u8))

    def _device_flow(self, group):
        t = group.shape[1] - 1  # T pairs from T+1 frames
        # np/jnp both work: raw host groups arrive as np, resized device
        # groups as jax arrays (rows sliced lazily). Multiple stacks' pair
        # batches fuse into ONE flow forward (k*T pairs): more queries per
        # launch amortize per-dispatch and per-scan-iteration fixed costs
        # (+45% stacks/s at 64f@224px going 1 -> 4, round-4 interleaved
        # A/B); k is geometry-budgeted so the correlation pyramid of a
        # large source cannot blow HBM (_stacks_per_forward).
        xp = jnp if not isinstance(group, np.ndarray) else np
        if self.stack_batch is not None:
            k = self.stack_batch
        elif self.parent.flow_type == "raft":
            k = _stacks_per_forward(t, *group.shape[2:4], self._budget)
        else:
            # PWC budget models the decoder live set, not RAFT's all-pairs
            # pyramid (_pwc_stacks_per_forward). Round-5 interleaved A/B
            # at 64f@224px on v5e: 1 -> 2 stacks/forward took bf16 PWC
            # from 6.78 to 11.33 stacks/s.
            k = _pwc_stacks_per_forward(
                t, *group.shape[2:4], self._budget,
                bytes_per_el=jnp.dtype(self._flow_dtype).itemsize)
        outs = []
        for i in range(0, len(group), k):
            chunk = group[i:i + k]            # (kc, T+1, H, W, 3)
            kc = chunk.shape[0]
            pairs = xp.stack([chunk[:, :-1], chunk[:, 1:]], axis=2)
            pairs = pairs.reshape((kc * t,) + pairs.shape[2:])
            # dispatch() keeps padded rows (the wire bucket may exceed
            # kc*t), so slice back to the valid pairs — a lazy device slice
            q = self.pair_runner.dispatch(pairs)[:kc * t]
            outs.append(q.reshape((kc, t) + q.shape[1:]))
        return jnp.concatenate(outs) if len(outs) > 1 else outs[0]
