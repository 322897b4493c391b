"""RAFT flow extractor.

Parity target: reference models/raft/extract_raft.py (+ base_flow_extractor):
sintel/kitti checkpoints, optional edge resize, replicate pad to /8
(InputPadder 'sintel' mode) before the net and unpad after
(base_flow_extractor.py:90, 108-114).
"""
from __future__ import annotations

from functools import partial

import jax.numpy as jnp

from ..config import Config
from ..models import raft as raft_model
from ..models.common import scope
from ..parallel.mesh import get_mesh
from ..weights import store
from .flow import OpticalFlowExtractor


def _raft_forward(model: raft_model.RAFT, params, pairs_u8):
    """(B, 2, H, W, 3) uint8 -> (B, H, W, 2) flow; pad/unpad inside jit."""
    with scope("RAFT", "encode"):
        pairs = pairs_u8.astype(jnp.float32)
    flow, ((pt, pb), (pl, pr)) = raft_model.padded_flow(model, params, pairs)
    hp, wp = flow.shape[1], flow.shape[2]
    with scope("RAFT", "upsample"):
        return flow[:, pt:hp - pb, pl:wp - pr, :].astype(jnp.float32)


class ExtractRAFT(OpticalFlowExtractor):

    def __init__(self, args: Config) -> None:
        super().__init__(args)
        finetuned_on = args.get("finetuned_on", "sintel")
        if finetuned_on not in ("sintel", "kitti"):
            raise NotImplementedError(
                f"finetuned_on={finetuned_on!r}; reference supports "
                "sintel/kitti (extract_raft.py:6-9)")
        # iters trades flow accuracy for speed (fewer GRU refinement steps);
        # default is the reference's fixed 20 (raft.py:118)
        raw = args.get("iters")
        iters = raft_model.ITERS if raw is None else int(raw)
        if iters < 1:
            raise ValueError(
                f"iters={iters}: RAFT needs at least one GRU refinement "
                "iteration")
        # precision=bfloat16: conv stacks on the MXU-native dtype (pyramid,
        # lookup and coords stay f32 — models/raft.py). ~0.1 px drift on
        # the output flow field; default f32 remains the bit-parity path.
        dtype = (jnp.bfloat16 if self.precision == "bfloat16"
                 else jnp.float32)
        self.model = raft_model.RAFT(iters=iters, dtype=dtype)
        params = store.resolve_params(
            f"raft_{finetuned_on}", raft_model.init_params,
            raft_model.params_from_torch,
            weights_path=args.get("weights_path"),
            allow_random=bool(args.get("allow_random_weights", False)))
        if dtype is not jnp.float32:
            from ..parallel.mesh import cast_floating
            params = cast_floating(params, dtype)
        mesh = self._data_mesh()
        self._init_flow_runner(partial(_raft_forward, self.model), params,
                               mesh)
