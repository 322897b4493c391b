"""R(2+1)D video networks (18/34-layer) as Flax modules, NDHWC.

The reference obtains these from torchvision (`r2plus1d_18`) and the IG-65M
torch.hub repo (34-layer flavors) at runtime — reference
models/r21d/extract_r21d.py:27-40,105-113 — so the architecture here is the
torchvision ``VideoResNet`` with the R(2+1)D factorized conv: each 3D conv is
a spatial (1,3,3) conv into ``midplanes`` channels followed by a temporal
(3,1,1) conv, with ``midplanes = (in*out*27) // (in*9 + 3*out)`` keeping the
parameter count of the full 3D conv.

Layout is (N, T, H, W, C): XLA tiles the last (channel) dim onto the MXU lane
axis and the factorized convs become large batched matmuls.

Weight transplant: :func:`params_from_torch` maps torchvision/IG-65M
state_dicts (``stem.0``, ``layerX.Y.conv1.0.0`` nested-Sequential keys) onto
this tree.
"""
from __future__ import annotations

from typing import Any, Dict, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from .common import BNInf
from ..weights import torch_import as ti

VARIANTS = {
    # model_name (reference extract_r21d.py:27-40) -> (stage blocks, default stack/step)
    "r2plus1d_18_16_kinetics": ((2, 2, 2, 2), 16),
    "r2plus1d_34_32_ig65m_ft_kinetics": ((3, 4, 6, 3), 32),
    "r2plus1d_34_8_ig65m_ft_kinetics": ((3, 4, 6, 3), 8),
}

FEATURE_DIM = 512
# K400 normalization used by the reference transform stack (extract_r21d.py:50-55)
R21D_MEAN = (0.43216, 0.394666, 0.37645)
R21D_STD = (0.22803, 0.22145, 0.216989)


def midplanes(in_planes: int, out_planes: int) -> int:
    return (in_planes * out_planes * 3 * 3 * 3) // (
        in_planes * 3 * 3 + 3 * out_planes)


def _conv3d(features: int, kernel: Tuple[int, int, int],
            stride: Tuple[int, int, int], pad: Tuple[int, int, int],
            name: str) -> nn.Conv:
    return nn.Conv(features, kernel, strides=stride,
                   padding=[(p, p) for p in pad], use_bias=False, name=name)


class Conv2Plus1D(nn.Module):
    """Factorized 3D conv: spatial (1,3,3) -> BN -> ReLU -> temporal (3,1,1)."""
    out_planes: int
    mid_planes: int
    stride: int = 1

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        s = self.stride
        x = _conv3d(self.mid_planes, (1, 3, 3), (1, s, s), (0, 1, 1), "conv_s")(x)
        x = BNInf(name="bn_mid")(x)
        x = nn.relu(x)
        x = _conv3d(self.out_planes, (3, 1, 1), (s, 1, 1), (1, 0, 0), "conv_t")(x)
        return x


class BasicBlock(nn.Module):
    planes: int
    stride: int = 1
    has_downsample: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        identity = x
        mid1 = midplanes(x.shape[-1], self.planes)
        out = Conv2Plus1D(self.planes, mid1, self.stride, name="conv1")(x)
        out = BNInf(name="bn1")(out)
        out = nn.relu(out)
        mid2 = midplanes(self.planes, self.planes)
        out = Conv2Plus1D(self.planes, mid2, 1, name="conv2")(out)
        out = BNInf(name="bn2")(out)
        if self.has_downsample:
            s = self.stride
            identity = _conv3d(self.planes, (1, 1, 1), (s, s, s), (0, 0, 0),
                               "downsample_conv")(x)
            identity = BNInf(name="downsample_bn")(identity)
        return nn.relu(out + identity)


class R2Plus1D(nn.Module):
    """Backbone: (N, T, H, W, 3) normalized float -> (N, 512) pooled features."""
    variant: str = "r2plus1d_18_16_kinetics"

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        stages, _ = VARIANTS[self.variant]
        # the stage scopes (stem, layer1..4, head) are metadata: a device
        # trace names every operation R2Plus1D/<stage>/<flax path>
        # R(2+1)D stem: spatial 7x7 then temporal 3 (torchvision R2Plus1dStem)
        with jax.named_scope("stem"):
            x = _conv3d(45, (1, 7, 7), (1, 2, 2), (0, 3, 3), "stem_conv_s")(x)
            x = BNInf(name="stem_bn_s")(x)
            x = nn.relu(x)
            x = _conv3d(64, (3, 1, 1), (1, 1, 1), (1, 0, 0), "stem_conv_t")(x)
            x = BNInf(name="stem_bn_t")(x)
            x = nn.relu(x)

        in_planes = 64
        for stage_idx, num_blocks in enumerate(stages):
            planes = 64 * (2 ** stage_idx)
            stride = 1 if stage_idx == 0 else 2
            for block_idx in range(num_blocks):
                s = stride if block_idx == 0 else 1
                needs_ds = (s != 1) or (in_planes != planes)
                with jax.named_scope(f"layer{stage_idx + 1}"):
                    x = BasicBlock(planes, s, needs_ds,
                                   name=f"layer{stage_idx + 1}_{block_idx}")(x)
                in_planes = planes
        # AdaptiveAvgPool3d(1)
        with jax.named_scope("head"):
            return jnp.mean(x, axis=(1, 2, 3))


class Classifier(nn.Module):
    """The Kinetics-400 fc head (kept aside for show_pred, reference
    extract_r21d.py:116-118)."""
    num_classes: int = 400

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        return nn.Dense(self.num_classes, name="fc")(x)


def init_params(variant: str = "r2plus1d_18_16_kinetics") -> Dict[str, Any]:
    """Random {'backbone', 'head'} trees — the msgpack template shape."""
    import jax
    backbone = R2Plus1D(variant).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4, 112, 112, 3)))["params"]
    head = Classifier().init(
        jax.random.PRNGKey(1), jnp.zeros((1, FEATURE_DIM)))["params"]
    return {"backbone": backbone, "head": head}


_BN_LEAF = {"weight": "scale", "bias": "bias",
            "running_mean": "mean", "running_var": "var"}

# nested-Sequential index -> our submodule name, inside one BasicBlock
_BLOCK_KEYMAP = {
    ("conv1", "0", "0"): ("conv1", "conv_s", "kernel"),
    ("conv1", "0", "1"): ("conv1", "bn_mid", None),
    ("conv1", "0", "3"): ("conv1", "conv_t", "kernel"),
    ("conv1", "1"): ("bn1", None),
    ("conv2", "0", "0"): ("conv2", "conv_s", "kernel"),
    ("conv2", "0", "1"): ("conv2", "bn_mid", None),
    ("conv2", "0", "3"): ("conv2", "conv_t", "kernel"),
    ("conv2", "1"): ("bn2", None),
    ("downsample", "0"): ("downsample_conv", "kernel"),
    ("downsample", "1"): ("downsample_bn", None),
}

_STEM_KEYMAP = {
    "0": ("stem_conv_s", "kernel"),
    "1": ("stem_bn_s", None),
    "3": ("stem_conv_t", "kernel"),
    "4": ("stem_bn_t", None),
}


def params_from_torch(state_dict: Mapping[str, Any]) -> Dict[str, Any]:
    """torchvision/IG-65M VideoResNet state_dict -> {'backbone','head'} trees."""
    backbone: Dict[str, Any] = {}
    head: Dict[str, Any] = {}
    for key, tensor in state_dict.items():
        if key.endswith("num_batches_tracked"):
            continue
        parts = key.split(".")
        if parts[0] == "fc":
            leaf = "kernel" if parts[1] == "weight" else "bias"
            val = ti.linear_kernel(tensor) if leaf == "kernel" else ti.to_np(tensor)
            ti.set_in(head, f"fc/{leaf}", val)
            continue
        if parts[0] == "stem":
            target, kind = _STEM_KEYMAP[parts[1]]
            if kind == "kernel":
                ti.set_in(backbone, f"{target}/kernel", ti.conv3d_kernel(tensor))
            else:
                ti.set_in(backbone, f"{target}/{_BN_LEAF[parts[2]]}",
                          ti.to_np(tensor))
            continue
        # layerX.Y.<nested sequential path>.<leaf>
        block = f"{parts[0]}_{parts[1]}"
        leaf = parts[-1]
        sub = tuple(parts[2:-1])
        mapped = _BLOCK_KEYMAP.get(sub)
        if mapped is None:
            raise KeyError(f"Unrecognized R(2+1)D checkpoint key: {key}")
        if mapped[-1] == "kernel":
            path = "/".join([block, *mapped[:-1], "kernel"])
            ti.set_in(backbone, path, ti.conv3d_kernel(tensor))
        else:
            path = "/".join([block, *mapped[:-1], _BN_LEAF[leaf]])
            ti.set_in(backbone, path, ti.to_np(tensor))
    return {"backbone": backbone, "head": head}
