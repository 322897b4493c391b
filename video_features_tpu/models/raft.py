"""RAFT optical flow as a JAX/Flax program, NHWC, static shapes.

Parity target: the reference's RAFT (reference models/raft/raft_src/
{raft,corr,update,extractor}.py, the princeton-vl network at 20 iterations,
test_mode — raft.py:118-177):

  - ``BasicEncoder`` fnet (instance norm, output 256) and cnet (batch norm,
    output 256 = 128 hidden + 128 context) at 1/8 resolution
    (extractor.py:116-189). Instance norms are affine-free and use batch
    statistics even at eval (torch InstanceNorm2d defaults), so they are
    pure functions here.
  - All-pairs correlation ``corr = <f1, f2> / sqrt(256)`` -> 4-level
    avg-pooled pyramid (corr.py:13-27). Same values, other order: each
    level is the correlation of ``f1`` against the 2x2-mean-pooled ``f2``
    (pooling is linear in the target index; the reference's
    AlternateCorrBlock, corr.py:63-91, pools ``fmap2`` too), so no pool
    ever runs over the all-pairs volume (:func:`build_corr_pyramid`).
  - Per-iteration windowed lookup (radius 4 -> 81 taps/level, 324 channels)
    via bilinear sampling with zeros padding + align_corners=True semantics
    (corr.py:29-50, utils/utils.py:59-73). The reference enumerates window
    taps with the x-offset varying slowest (its meshgrid(dy,dx) quirk adds
    "dy" to x) — replicated exactly so the 324 channels line up with the
    pretrained motion-encoder weights.
  - ``BasicUpdateBlock``: motion encoder convs, two-pass (1,5)/(5,1)
    ``SepConvGRU``, flow head, and a 9-way convex-upsample mask scaled by
    0.25 (update.py:86-144).
  - 20 GRU iterations as a ``lax.scan`` (XLA compiles the loop body once);
    the convex 8x upsample runs once on the final flow instead of per
    iteration (the reference computes it every iteration and discards all
    but the last, raft.py:154-175 — same result, 19 fewer upsamples).

Design notes (TPU): everything is fixed-shape; the correlation volume is the
memory hot spot (B * (HW/64)^2 floats) exactly as in the reference — and a
layout hot spot: its minor dims are a level's (Hl, Wl), so every one of the
B * HW/64 planes is at least one padded (8, 128) tile and anything that
walks the volume pays per plane (pooling it for the pyramid measured a
third of the step on a v5e; the pools run on the feature maps). The gather
lookup is 4 ``take_along_axis`` gathers per corner which XLA lowers to
dynamic-gather — no data-dependent shapes anywhere.

Input images: (B, H, W, 3) float32 in [0, 255]; H, W divisible by 8
(callers pad with ``pad_to_multiple`` replicate padding = the reference's
InputPadder, raft.py:30-48). Output: (B, H, W, 2) flow in pixels.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from .common import BNInf, scope
from ..kernels import corr_lookup as lookup_kernels, interpret_mode
from ..kernels.corr_lookup import LookupForm
from ..weights import torch_import as ti

CORR_LEVELS = 4
CORR_RADIUS = 4
HIDDEN_DIM = 128
CONTEXT_DIM = 128
ITERS = 20


def instance_norm(x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """torch InstanceNorm2d(affine=False, track_running_stats=False) at eval:
    per-sample, per-channel normalization over H, W with biased variance.
    Statistics accumulate in f32 regardless of activation dtype (bf16 mode
    keeps the convs on the MXU-native dtype, norm internals stay exact)."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=(1, 2), keepdims=True)
    var = jnp.var(x32, axis=(1, 2), keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps)).astype(x.dtype)


class ResidualBlock(nn.Module):
    planes: int
    norm_fn: str  # 'instance' | 'batch' | 'none'
    stride: int = 1

    def _norm(self, name: str, x: jnp.ndarray) -> jnp.ndarray:
        if self.norm_fn == "batch":
            return BNInf(name=name)(x)
        if self.norm_fn == "instance":
            return instance_norm(x)
        return x

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        y = nn.Conv(self.planes, (3, 3), strides=self.stride,
                    padding=1, name="conv1")(x)
        y = nn.relu(self._norm("norm1", y))
        y = nn.Conv(self.planes, (3, 3), padding=1, name="conv2")(y)
        y = nn.relu(self._norm("norm2", y))
        if self.stride != 1:
            x = nn.Conv(self.planes, (1, 1), strides=self.stride,
                        name="downsample_0")(x)
            x = self._norm("downsample_1", x)
        return nn.relu(x + y)


class BasicEncoder(nn.Module):
    """extractor.py:116-189; all convs carry bias (torch default)."""
    output_dim: int
    norm_fn: str

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x = nn.Conv(64, (7, 7), strides=2, padding=3, name="conv1")(x)
        if self.norm_fn == "batch":
            x = BNInf(name="norm1")(x)
        elif self.norm_fn == "instance":
            x = instance_norm(x)
        x = nn.relu(x)
        for i, (dim, stride) in enumerate([(64, 1), (96, 2), (128, 2)]):
            x = ResidualBlock(dim, self.norm_fn, stride,
                              name=f"layer{i + 1}_0")(x)
            x = ResidualBlock(dim, self.norm_fn, 1, name=f"layer{i + 1}_1")(x)
        return nn.Conv(self.output_dim, (1, 1), name="conv2")(x)


class _Convc1Params(nn.Module):
    """Parameter-only twin of ``nn.Conv(256, (1, 1), name='convc1')`` —
    identical tree path, shapes, and init, so weight transplant and
    checkpoints are unchanged; the conv itself runs inside the fused
    Pallas lookup+projection kernel (kernels/corr_lookup.py
    corr_lookup_proj)."""
    features: int = 256
    in_features: int = CORR_LEVELS * (2 * CORR_RADIUS + 1) ** 2

    @nn.compact
    def __call__(self):
        k = self.param("kernel", nn.initializers.lecun_normal(),
                       (1, 1, self.in_features, self.features))
        b = self.param("bias", nn.initializers.zeros, (self.features,))
        return k, b


class BasicMotionEncoder(nn.Module):
    """update.py:86-104.

    ``lookup`` (static) says what ``corr`` is. Under ``proj`` it is the
    one pyramid plane a query (kernels/corr_lookup.py
    stack_aligned_pyramid) and ``coords`` the level-0 query centers:
    convc1 runs inside the fused Pallas lookup+projection kernel and the
    (B, H, W, 324) lookup intermediate never materializes (round-4
    profiling: its relayout boundary cost ~17 ms per 64-pair forward on
    v5e). Under every other form it is that intermediate."""
    lookup: LookupForm = LookupForm("gather")

    @nn.compact
    def __call__(self, flow: jnp.ndarray, corr: jnp.ndarray,
                 coords: jnp.ndarray) -> jnp.ndarray:
        if self.lookup.impl == "proj":
            k, b = _Convc1Params(name="convc1")()
            with jax.named_scope("lookup"):
                cor = lookup_kernels.corr_lookup_proj(
                    corr, self.lookup.metas, coords,
                    k.reshape(k.shape[2], k.shape[3]), b,
                    interpret=interpret_mode())
                cor = cor.astype(flow.dtype)
        else:
            cor = nn.relu(nn.Conv(256, (1, 1), name="convc1")(corr))
        cor = nn.relu(nn.Conv(192, (3, 3), padding=1, name="convc2")(cor))
        flo = nn.relu(nn.Conv(128, (7, 7), padding=3, name="convf1")(flow))
        flo = nn.relu(nn.Conv(64, (3, 3), padding=1, name="convf2")(flo))
        out = nn.relu(nn.Conv(126, (3, 3), padding=1, name="conv")(
            jnp.concatenate([cor, flo], axis=-1)))
        return jnp.concatenate([out, flow], axis=-1)


class SepConvGRU(nn.Module):
    """Two-pass separable GRU (update.py:39-65)."""
    hidden_dim: int = HIDDEN_DIM

    @nn.compact
    def __call__(self, h: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
        for suffix, kernel, pad in (("1", (1, 5), (0, 2)), ("2", (5, 1), (2, 0))):
            hx = jnp.concatenate([h, x], axis=-1)
            pad2 = [(pad[0], pad[0]), (pad[1], pad[1])]
            z = nn.sigmoid(nn.Conv(self.hidden_dim, kernel, padding=pad2,
                                   name=f"convz{suffix}")(hx))
            r = nn.sigmoid(nn.Conv(self.hidden_dim, kernel, padding=pad2,
                                   name=f"convr{suffix}")(hx))
            q = jnp.tanh(nn.Conv(self.hidden_dim, kernel, padding=pad2,
                                 name=f"convq{suffix}")(
                jnp.concatenate([r * h, x], axis=-1)))
            h = (1 - z) * h + z * q
        return h


class FlowHead(nn.Module):
    hidden_dim: int = 256

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x = nn.relu(nn.Conv(self.hidden_dim, (3, 3), padding=1,
                            name="conv1")(x))
        return nn.Conv(2, (3, 3), padding=1, name="conv2")(x)


class UpdateIter(nn.Module):
    """One RAFT iteration: corr lookup + BasicUpdateBlock (update.py:123-144;
    the mask head is applied separately, see RAFT.__call__). Shaped as a
    ``lax.scan`` body: (carry, broadcast-inputs) -> (carry, None).

    ``lookup`` (static) is the form :func:`kernels.corr_lookup.prepare_lookup`
    handed the broadcast ``pyramid`` input in; the body runs that form and
    decides nothing."""
    lookup: LookupForm = LookupForm("gather")

    @nn.compact
    def __call__(self, carry, inputs):
        net, coords1 = carry
        pyramid, inp, coords0 = inputs
        flow = (coords1 - coords0).astype(net.dtype)
        if self.lookup.impl == "proj":
            corr = pyramid  # the encoder's fused kernel looks up and projects
        else:
            # the lookup runs in f32 (coords + pyramid precision); under
            # bf16 mode its (B,H,W,324) output and the flow join the hidden
            # state's dtype so the update convs stay on the MXU-native
            # dtype. coords stay f32 through the carry: delta promotes back
            # on add.
            with jax.named_scope("lookup"):
                if self.lookup.impl == "level":
                    corr = lookup_kernels.corr_lookup_pallas(
                        pyramid, coords1, CORR_RADIUS,
                        interpret=interpret_mode())
                elif self.lookup.impl == "onehot":
                    corr = lookup_kernels.corr_lookup_onehot(
                        pyramid, coords1, CORR_RADIUS)
                else:
                    corr = corr_lookup_gather(pyramid, coords1)
                corr = corr.astype(net.dtype)
        motion = BasicMotionEncoder(lookup=self.lookup, name="encoder")(
            flow, corr, coords1)
        x = jnp.concatenate([inp, motion], axis=-1)
        net = SepConvGRU(name="gru")(net, x)
        delta = FlowHead(name="flow_head")(net)
        return (net, coords1 + delta.astype(coords1.dtype)), None


class MaskHead(nn.Module):
    """update.py:130-133 (`update_block.mask` Sequential) with the 0.25
    gradient-balance scale from update.py:143."""

    @nn.compact
    def __call__(self, net: jnp.ndarray) -> jnp.ndarray:
        x = nn.relu(nn.Conv(256, (3, 3), padding=1, name="mask_0")(net))
        return 0.25 * nn.Conv(64 * 9, (1, 1), name="mask_2")(x)


# ---- correlation volume --------------------------------------------------

def _pooled_fmaps(fmap2: jnp.ndarray, num_levels: int) -> List[jnp.ndarray]:
    """The second feature map at every pyramid level, (B, Hl, Wl, C): level
    0 as it came (bf16 in bf16 mode), every further level the float32 2x2
    mean of the one before under torch avg_pool2d's floor rule (an odd
    trailing row/col is dropped; a 1-wide level pools to a 0-sized one)."""
    levels = [fmap2]
    pooled = fmap2.astype(jnp.float32)
    for _ in range(num_levels - 1):
        hl, wl = pooled.shape[1] // 2 * 2, pooled.shape[2] // 2 * 2
        pooled = jax.lax.reduce_window(
            pooled[:, :hl, :wl], 0.0, jax.lax.add, (1, 2, 2, 1),
            (1, 2, 2, 1), [(0, 0)] * 4) / 4.0
        levels.append(pooled)
    return levels


def build_corr_pyramid(fmap1: jnp.ndarray, fmap2: jnp.ndarray,
                       num_levels: int = CORR_LEVELS) -> List[jnp.ndarray]:
    """All-pairs correlation pyramid (corr.py:13-27, 52-60).

    fmaps: (B, H, W, C). Returns per level float32 (B, H*W, Hl, Wl).

    Every level is a correlation against the POOLED second feature map,
    never a pool of the volume: average pooling is linear and acts on the
    target index q only, so pool(<f1[p], f2[q]>) = <f1[p], pool(f2)[q']>
    (the identity the reference's own AlternateCorrBlock uses,
    corr.py:63-91). The pools then run over channel-minor feature maps,
    0.6 MB a pair at 240x320. Pooling the (B, P, Hl, Wl) volume instead
    cost a third of the RAFT step on a v5e: its minor dims (30, 40) ...
    (3, 5) make every one of the B*P planes a padded (8, 128) tile, and
    each reduce-window paid per plane, under 1% of its memory roofline."""
    b, h, w, c = fmap1.shape
    f1 = fmap1.reshape(b, h * w, c)
    root = math.sqrt(c)
    # a power of two (C = 256: 16) divides f1 exactly in every dtype, which
    # saves the pass over the volume; any other root divides the float32
    # result
    scale_f1 = math.frexp(root)[0] == 0.5
    if scale_f1:
        f1 = f1 / jnp.asarray(root, f1.dtype)
    pyramid = []
    for lvl, f2 in enumerate(_pooled_fmaps(fmap2, num_levels)):
        hl, wl = f2.shape[1:3]
        # level 0: operands as they came, f32 accumulation/output even from
        # bf16 fmaps — the pyramid (and hence the lookup) keeps full
        # precision in every mode and the MXU still takes bf16 inputs at
        # native rate. A pooled f2 is a float32 mean and has to reach the
        # contraction unrounded: float32 operands under HIGHEST (the MXU's
        # default pass would round it to bf16, 1e-3 of max|corr|)
        pooled = lvl > 0
        corr = jnp.einsum(
            "bpc,bqc->bpq", f1.astype(jnp.float32) if pooled else f1,
            f2.reshape(b, hl * wl, c), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST if pooled else None)
        if not scale_f1:
            corr = corr / root
        # contract over the flattened target index and reshape after: the
        # compiler then re-tiles the true-size volume to p-major and the
        # pads of the lookup's plane write its final layout (a
        # "bpc,bhwc->bphw" dot keeps p minor through the pads and pays a
        # second pass over the 6.8x larger padded plane, measured on v5e)
        pyramid.append(corr.reshape(b, h * w, hl, wl))
    return pyramid


def _state_corr_lookup(form: LookupForm) -> None:
    """Trace-time record of the lookup a forward was built with. Feature
    values are the same on every form, so the form has to be stated: no
    output check can tell. A ``corr_lookup`` event on the current video
    span (telemetry=true) with ``impl``, ``compiled`` (True when
    ``pallas_call`` goes to Mosaic, False when it runs in the Pallas
    interpreter off a TPU, None for the XLA forms), ``fallback`` and, under
    ``proj``, ``plane_cells`` (cells of the plane the kernel reads a query
    and iteration) and ``plane_fill`` (the share of them that hold data;
    None under the other forms); and a printed line whenever a size gate
    replaced ``proj``. Runs once per traced shape, not per call."""
    from .. import telemetry
    kernel = form.impl in ("proj", "level")
    cells, fill = lookup_kernels.plane_fill(form.metas)
    telemetry.event("corr_lookup", impl=form.impl,
                    compiled=(not interpret_mode()) if kernel else None,
                    fallback=form.fallback, plane_cells=cells,
                    plane_fill=fill)
    if form.fallback is not None:
        print(f"corr lookup: impl={form.impl} in place of proj: "
              f"{form.fallback}")


def corr_lookup_gather(pyramid: Sequence[jnp.ndarray], coords: jnp.ndarray,
                       radius: int = CORR_RADIUS) -> jnp.ndarray:
    """Windowed bilinear lookup (corr.py:29-50).

    coords: (B, H, W, 2) (x, y) at level-0 resolution. Returns
    (B, H, W, levels*(2r+1)^2) with the reference's channel order: per level,
    the x-offset varies slowest across the 81 taps (corr.py:37-43 adds its
    meshgrid's dy to the x coordinate), then levels are concatenated.
    """
    b, h, w, _ = coords.shape
    p = h * w
    n_taps = (2 * radius + 1) ** 2
    d = jnp.linspace(-radius, radius, 2 * radius + 1, dtype=jnp.float32)
    off_slow = jnp.repeat(d, 2 * radius + 1)  # added to x (the dy quirk)
    off_fast = jnp.tile(d, 2 * radius + 1)    # added to y
    cx = coords[..., 0].reshape(b, p, 1)
    cy = coords[..., 1].reshape(b, p, 1)

    out = []
    for lvl, corr in enumerate(pyramid):
        hl, wl = corr.shape[2], corr.shape[3]
        corr_flat = corr.reshape(b, p, hl * wl)
        x = cx / (2 ** lvl) + off_slow  # (B, P, 81)
        y = cy / (2 ** lvl) + off_fast
        x0 = jnp.floor(x)
        y0 = jnp.floor(y)
        wx1 = x - x0
        wy1 = y - y0
        acc = jnp.zeros((b, p, n_taps), dtype=corr.dtype)
        for xi, wxf in ((x0, 1.0 - wx1), (x0 + 1, wx1)):
            for yi, wyf in ((y0, 1.0 - wy1), (y0 + 1, wy1)):
                # zeros padding: out-of-range corners contribute nothing
                valid = ((xi >= 0) & (xi <= wl - 1) &
                         (yi >= 0) & (yi <= hl - 1))
                idx = (jnp.clip(yi, 0, hl - 1) * wl +
                       jnp.clip(xi, 0, wl - 1)).astype(jnp.int32)
                val = jnp.take_along_axis(corr_flat, idx, axis=2)
                acc = acc + jnp.where(valid, wxf * wyf * val, 0.0)
        out.append(acc.reshape(b, h, w, n_taps))
    return jnp.concatenate(out, axis=-1)


def convex_upsample(flow: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Learned 8x convex-combination upsample (raft.py:104-115), NHWC.

    flow: (B, H, W, 2); mask: (B, H, W, 576). Returns (B, 8H, 8W, 2)."""
    b, h, w, _ = flow.shape
    # f32 softmax + combination even from a bf16 mask head
    mask = mask.astype(jnp.float32).reshape(b, h, w, 9, 8, 8)
    mask = jax.nn.softmax(mask, axis=3)
    # 3x3 neighborhoods of 8*flow (torch F.unfold k=3 pad=1, row-major taps)
    fpad = jnp.pad(8.0 * flow, ((0, 0), (1, 1), (1, 1), (0, 0)))
    taps = [fpad[:, dy:dy + h, dx:dx + w, :]
            for dy in range(3) for dx in range(3)]
    nb = jnp.stack(taps, axis=3)  # (B, H, W, 9, 2)
    up = jnp.einsum("bhwkij,bhwkc->bhwijc", mask, nb)  # (B, H, W, 8, 8, 2)
    return up.transpose(0, 1, 3, 2, 4, 5).reshape(b, 8 * h, 8 * w, 2)


def pad_to_multiple(x: np.ndarray, mult: int = 8,
                    mode: str = "sintel") -> Tuple[Tuple[int, int], Tuple[int, int]]:
    """InputPadder pad amounts (raft.py:30-40) for an (..., H, W, C) shape.

    Returns ((top, bottom), (left, right)) replicate-pad amounts."""
    ht, wd = x.shape[-3], x.shape[-2]
    pad_ht = (((ht // mult) + 1) * mult - ht) % mult
    pad_wd = (((wd // mult) + 1) * mult - wd) % mult
    if mode == "sintel":
        return ((pad_ht // 2, pad_ht - pad_ht // 2),
                (pad_wd // 2, pad_wd - pad_wd // 2))
    return ((0, pad_ht), (pad_wd // 2, pad_wd - pad_wd // 2))


def padded_flow(model: "RAFT", params, pairs_f32: jnp.ndarray,
                mode: str = "sintel"):
    """Run RAFT on an (B, 2, H, W, 3) float pair batch with InputPadder
    semantics (replicate-pad to /8, raft.py:30-48). Returns the flow at
    *padded* resolution plus the ((top, bottom), (left, right)) pad amounts
    so callers can unpad (extract_raft) or center-crop the padded field
    (the I3D flow stream, which never unpads — extract_i3d.py:153)."""
    (pt, pb), (pl, pr) = pad_to_multiple(pairs_f32[:, 0], mode=mode)
    pad = ((0, 0), (pt, pb), (pl, pr), (0, 0))
    with scope("RAFT", "encode"):
        image1 = jnp.pad(pairs_f32[:, 0], pad, mode="edge")
        image2 = jnp.pad(pairs_f32[:, 1], pad, mode="edge")
    flow = model.apply({"params": params}, image1, image2)
    return flow, ((pt, pb), (pl, pr))


class RAFT(nn.Module):
    """(B, H, W, 3) [0,255] image pairs -> (B, H, W, 2) flow (pixels).

    ``dtype=jnp.bfloat16`` (with params cast via ``cast_floating``) runs the
    conv stacks — encoders, motion encoder, GRU, flow/mask heads — in the
    MXU-native dtype while the precision-critical state stays f32: the corr
    pyramid (f32-accumulated einsum), the lookup, the iterated coords, norm
    statistics, and the upsample softmax. The pyramid's pooled levels are
    float32 means of ``fmap2`` contracted under ``Precision.HIGHEST``
    (build_corr_pyramid): level 0 is bit-identical to the pooled-volume
    form's, levels 1-3 within 1e-7 of max|corr| (chip, PR 25) — the pools
    moved, the precision did not. Flow drift vs f32 is sub-0.1 px
    (well under the I3D flow stream's ToUInt8 quantization step of ~0.16);
    the f32 default is bit-identical to before (every cast is a no-op).

    The pyramid stays f32 in every mode. Since its levels share one
    lane-padded plane the fused lookup runs at the memory wall of that
    plane (16,384 B a query and iteration at 240x320, 662 GB/s of a v5e's
    819; kernels/corr_lookup.py), so a bf16 pyramid would now buy time,
    and would cost the lookup its exact values: a different result, not
    a faster one."""
    iters: int = ITERS
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, image1: jnp.ndarray, image2: jnp.ndarray) -> jnp.ndarray:
        # the stage scopes (encode, corr_pyramid, update, upsample) are
        # metadata: a device trace names every operation
        # RAFT/<stage>/<flax path>; the lookup adds update/.../lookup
        with jax.named_scope("encode"):
            image1 = (2 * (image1 / 255.0) - 1.0).astype(self.dtype)
            image2 = (2 * (image2 / 255.0) - 1.0).astype(self.dtype)

            fnet = BasicEncoder(256, "instance", name="fnet")
            # one shared-weight call on the concatenated pair, like the
            # reference's fnet([image1, image2]) (raft.py:132)
            fmaps = fnet(jnp.concatenate([image1, image2], axis=0))
            fmap1, fmap2 = jnp.split(fmaps, 2, axis=0)
        with jax.named_scope("corr_pyramid"):
            pyramid = build_corr_pyramid(fmap1, fmap2)
            # the lookup's one decision, and the loop-invariant pads it
            # brings, ONCE outside the scan (kernels/corr_lookup.py
            # prepare_lookup). With them the stage is four small dots, a
            # re-tiling of each true-size level and the pads; the volume is
            # never pooled
            pyramid, lookup = lookup_kernels.prepare_lookup(pyramid)
        _state_corr_lookup(lookup)

        with jax.named_scope("encode"):
            cnet = BasicEncoder(HIDDEN_DIM + CONTEXT_DIM, "batch",
                                name="cnet")(image1)
            net = jnp.tanh(cnet[..., :HIDDEN_DIM])
            inp = nn.relu(cnet[..., HIDDEN_DIM:])

        with jax.named_scope("update"):
            b, h8, w8, _ = net.shape
            gx, gy = jnp.meshgrid(jnp.arange(w8, dtype=jnp.float32),
                                  jnp.arange(h8, dtype=jnp.float32))
            coords0 = jnp.broadcast_to(jnp.stack([gx, gy], axis=-1),
                                       (b, h8, w8, 2))

            # lax.scan compiles ONE iteration body regardless of iters; the
            # reference's Python loop (raft.py:154-171) unrolls 20 copies
            scanned = nn.scan(
                UpdateIter, variable_broadcast="params",
                split_rngs={"params": False}, in_axes=nn.broadcast,
                length=self.iters)(lookup=lookup, name="update_block")
            (net, coords1), _ = scanned((net, coords0),
                                        (pyramid, inp, coords0))

        with jax.named_scope("upsample"):
            mask = MaskHead(name="update_mask")(net)
            return convex_upsample(coords1 - coords0, mask)


# ---- weight transplant ---------------------------------------------------

def params_from_torch(state_dict: Mapping[str, Any]) -> Dict[str, Any]:
    """raft-{sintel,kitti}.pth state_dict -> Flax tree.

    torch key layout: ``{fnet,cnet}.{conv1,conv2,layerL.I.*}``,
    ``update_block.{encoder,gru,flow_head,mask.N}``. BN modules are detected
    by their ``running_mean``; ``normK`` keys duplicate ``downsample.1`` in
    torch (same module registered under two names) and are skipped.
    """
    state_dict = ti.strip_module_prefix(state_dict)  # DataParallel ckpts
    params: Dict[str, Any] = {}
    for key, tensor in state_dict.items():
        if key.endswith("num_batches_tracked"):
            continue
        parts = key.split(".")
        leaf = parts[-1]
        mods = parts[:-1]
        # norm3/norm4 duplicate downsample.1 (extractor.py:44-45)
        if any(m in ("norm3", "norm4") for m in mods):
            continue
        # merge Sequential indices into the parent name: layer1.0 ->
        # layer1_0, downsample.0 -> downsample_0, mask.0 -> mask_0
        flat: List[str] = []
        for m in mods:
            if m.isdigit() and flat:
                flat[-1] = f"{flat[-1]}_{m}"
            else:
                flat.append(m)
        # the mask Sequential lives beside the update block in our tree
        if flat[0] == "update_block" and flat[1].startswith("mask_"):
            flat = ["update_mask"] + flat[1:]
        module = flat[-1]
        prefix = "/".join(flat[:-1])
        is_bn = f"{'.'.join(mods)}.running_mean" in state_dict
        if is_bn:
            bnl = {"weight": "scale", "bias": "bias",
                   "running_mean": "mean", "running_var": "var"}[leaf]
            ti.set_in(params, f"{prefix}/{module}/{bnl}", ti.to_np(tensor))
        elif leaf == "weight":
            ti.set_in(params, f"{prefix}/{module}/kernel",
                      ti.conv2d_kernel(tensor))
        else:
            ti.set_in(params, f"{prefix}/{module}/bias", ti.to_np(tensor))
    return params


def init_params(iters: int = ITERS) -> Dict[str, Any]:
    model = RAFT(iters=iters)
    v = model.init(jax.random.PRNGKey(0),
                   jnp.zeros((1, 64, 64, 3)), jnp.zeros((1, 64, 64, 3)))
    return v["params"]
