"""RAFT: operations and bytes one frame pair needs at the served geometry.

Walks the published architecture (Teed & Deng 2020, ``raft-sintel``) as
``configs/raft-sintel.json`` states it: a residual feature encoder on both
frames and a context encoder on the first, all at 1/8 resolution; the
all-pairs correlation volume and its pooled pyramid in float32; then, for
every refinement iteration, a lookup of (2r+1)^2 bilinear samples at each
pyramid level, the motion encoder, the separable ConvGRU and the flow head;
last the mask head and convex upsampling.

``kernels.corr_lookup`` is the part the fused Pallas kernel computes (lookup
and the motion encoder's first 1x1 projection), all iterations.
"""
from vftbench.shapes import Tally, out_len


def encoder(t: Tally, name: str, h: int, w: int, out_dim: int, images: int):
    """BasicEncoder: 7x7/2 stem, three stages of two residual blocks
    (64, 96/2, 128/2), 1x1 to ``out_dim``."""
    h2, w2 = out_len(h, 7, 2, 3), out_len(w, 7, 2, 3)
    t.conv(f"{name}.conv1", h * w, h2 * w2, 49, 3, 64, images)
    cin, hh, ww = 64, h2, w2
    for si, (dim, stride) in enumerate(((64, 1), (96, 2), (128, 2))):
        ho, wo = out_len(hh, 3, stride, 1), out_len(ww, 3, stride, 1)
        t.conv(f"{name}.layer{si + 1}", hh * ww, ho * wo, 9, cin, dim, images)
        t.conv(f"{name}.layer{si + 1}", ho * wo, ho * wo, 9, dim, dim,
               3 * images)
        if stride != 1:
            t.conv(f"{name}.layer{si + 1}", hh * ww, ho * wo, 1, cin, dim,
                   images)
        cin, hh, ww = dim, ho, wo
    t.conv(f"{name}.conv2", hh * ww, hh * ww, 1, cin, out_dim, images)
    return hh, ww


def per_unit(config):
    arch = config["architecture"]
    h, w = int(arch["height"]), int(arch["width"])
    iters, levels = int(arch["iters"]), int(arch["corr_levels"])
    taps = (2 * int(arch["corr_radius"]) + 1) ** 2
    hidden, context = int(arch["hidden_dim"]), int(arch["context_dim"])
    fdim = int(arch["feature_dim"])
    t = Tally(act_bytes=2)
    t.extra("wire", 0.0, 2 * h * w * 3)
    h8, w8 = encoder(t, "fnet", h, w, fdim, images=2)
    encoder(t, "cnet", h, w, hidden + context, images=1)
    p = h8 * w8
    # all-pairs correlation, float32 out, then the pooled pyramid
    pyramid = sum(p * (h8 >> lv) * (w8 >> lv) for lv in range(levels))
    t.extra("corr_volume", 2.0 * p * p * fdim,
            2 * p * fdim * 2 + p * p * 4 + (pyramid - p * p) * 4 * 2)
    # per iteration: 4 taps of a bilinear sample = 4 multiplies + 3 adds + 1
    lookup_flops = p * levels * taps * 8.0
    lookup_bytes = p * levels * taps * 4 * 4 + p * 2 * 4
    corr_ch = levels * taps
    t.extra("lookup", iters * lookup_flops, iters * lookup_bytes)
    t.conv("motion.convc1", 0, p, 1, corr_ch, 256, iters)
    t.conv("motion.convc2", p, p, 9, 256, 192, iters)
    t.conv("motion.convf1", p, p, 49, 2, 128, iters)
    t.conv("motion.convf2", p, p, 9, 128, 64, iters)
    t.conv("motion.conv", p, p, 9, 192 + 64, 126, iters)
    gru_in = hidden + context + 128
    t.conv("gru", p, p, 5, gru_in, hidden, 6 * iters)
    t.conv("flow_head", p, p, 9, hidden, 256, iters)
    t.conv("flow_head", p, p, 9, 256, 2, iters)
    t.conv("mask_head", p, p, 9, hidden, 256)
    t.conv("mask_head", p, p, 1, 256, 64 * 9)
    # convex upsampling: 9 weighted coarse flows for each fine pixel, 2 ch
    t.extra("upsample", h * w * 9 * 2 * 2.0, p * 64 * 9 * 2 + h * w * 2 * 4)
    batch = int(config["run_keys"][config["batch_key"]])
    kernel = {"flops": iters * (lookup_flops + 2.0 * p * corr_ch * 256),
              "bytes": iters * (lookup_bytes + p * 256 * 2)
              + corr_ch * 256 * 2 / batch}
    return {**t.per_unit(batch), "layers": t.layers,
            "kernels": {"corr_lookup": kernel}}
