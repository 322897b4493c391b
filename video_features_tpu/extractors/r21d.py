"""R(2+1)D clip-stack extractor.

Parity target: reference models/r21d/extract_r21d.py — three model flavors
with per-flavor default stack/step (16/16, 32/32, 8/8), transform stack
[0,1]-float -> bilinear Resize(128,171) (non-antialiased) -> K400 Normalize ->
CenterCrop(112) (extract_r21d.py:50-55), fc swapped for Identity with the
Kinetics head kept for show_pred. Output key: ['r21d'] only
(extract_r21d.py:57).
"""
from __future__ import annotations

from functools import partial

import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..models import r21d as r21d_model
from ..models.common import scope
from ..ops import colorspace
from ..ops import host_transforms as ht
from ..ops import preprocess as pp
from ..parallel.mesh import DataParallelApply, cast_floating, get_mesh
from ..utils.labels import show_predictions_on_dataset
from ..weights import store
from .clip_stack import ClipStackExtractor


def _device_forward(model: r21d_model.R2Plus1D, dtype, params, batch):
    """(B, T, 112, 112, 3) float [0,1] or uint8 -> (B, 512).

    /255 (uint8 wire format only), K400-normalize, backbone — all fused by
    XLA into the stem conv. The dtype branch is resolved at trace time.
    """
    with scope("R2Plus1D", "ingest"):
        if batch.dtype == jnp.uint8:
            batch = batch.astype(jnp.float32) / 255.0
        x = (batch - jnp.asarray(r21d_model.R21D_MEAN, batch.dtype)) / \
            jnp.asarray(r21d_model.R21D_STD, batch.dtype)
        x = x.astype(dtype)
    feats = model.apply({"params": params}, x)
    with scope("R2Plus1D", "head"):
        return feats.astype(jnp.float32)


def _device_forward_yuv420(model: r21d_model.R2Plus1D, dtype, params, batch):
    """Packed-I420 uint8 (B, T, 112*112*3/2) -> (B, 512).

    On-device colorspace conversion (ops/colorspace.py) into the shared
    normalize + backbone; the wire carries 1.5 bytes/pixel instead of 3.
    """
    with scope("R2Plus1D", "ingest"):
        rgb = colorspace.yuv420_packed_to_rgb(batch, 112, 112) / 255.0
    return _device_forward(model, dtype, params, rgb)


class ExtractR21D(ClipStackExtractor):

    supported_ingest = ("yuv420", "uint8", "float32")
    frame_channel_order = "bgr"  # RGB reorder deferred into the transform

    def __init__(self, args: Config) -> None:
        if args.model_name not in r21d_model.VARIANTS:
            raise NotImplementedError(f"Model {args.model_name} not found.")
        _, default_stack = r21d_model.VARIANTS[args.model_name]
        super().__init__(args, default_stack=default_stack,
                         default_step=default_stack)

        self.model = r21d_model.R2Plus1D(self.model_name)
        self.head = r21d_model.Classifier()

        params = store.resolve_params(
            self.model_name,
            partial(r21d_model.init_params, self.model_name),
            r21d_model.params_from_torch,
            weights_path=args.get("weights_path"),
            allow_random=bool(args.get("allow_random_weights", False)))
        self.head_params = params["head"]

        dtype = jnp.bfloat16 if self.precision == "bfloat16" else jnp.float32
        mesh = self._data_mesh()
        fwd = (_device_forward_yuv420 if self.ingest == "yuv420"
               else _device_forward)
        self.runner = DataParallelApply(
            partial(fwd, self.model, dtype),
            cast_floating(params["backbone"], dtype),
            mesh=mesh, fixed_batch=self.clip_batch_size)

        # a picklable callable (ops/host_transforms.py), not a closure:
        # video_decode=process ships it to spawned decode workers
        self.host_transform = ht.R21DTransform(self.ingest)

    def maybe_show_pred(self, feats: np.ndarray, slices, group=None) -> None:
        if self.show_pred:
            logits = np.asarray(self.head.apply({"params": self.head_params},
                                                jnp.asarray(feats)))
            for row, (s, e) in zip(logits, slices):
                print(f"At frames ({s}, {e})")
                show_predictions_on_dataset(row[None], "kinetics")
