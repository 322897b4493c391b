"""R(2+1)D-18: operations and bytes one 16 x 112 x 112 clip needs.

Walks the published architecture (Tran et al. 2018; torchvision
``r2plus1d_18``) as ``configs/r21d-18.json`` states it: a (1,7,7) then (3,1,1)
stem, four stages of two basic blocks, every 3-D convolution factored into a
(1,3,3) spatial convolution into ``midplanes`` channels and a (3,1,1) temporal
one, 1x1x1 projections where a stage changes shape, global average pooling.
"""
from vftbench.shapes import Tally, out_len


def midplanes(cin: int, cout: int) -> int:
    return (cin * cout * 3 * 3 * 3) // (cin * 3 * 3 + 3 * cout)


def conv2plus1d(t: Tally, name: str, dims, cin: int, cout: int, stride: int):
    """(1,3,3) stride (1,s,s) into midplanes, then (3,1,1) stride (s,1,1)."""
    frames, h, w = dims
    mid = midplanes(cin, cout)
    h2, w2 = out_len(h, 3, stride, 1), out_len(w, 3, stride, 1)
    t.conv(f"{name}.spatial", frames * h * w, frames * h2 * w2, 9, cin, mid)
    f2 = out_len(frames, 3, stride, 1)
    t.conv(f"{name}.temporal", frames * h2 * w2, f2 * h2 * w2, 3, mid, cout)
    return f2, h2, w2


def per_unit(config):
    arch = config["architecture"]
    frames, size = int(arch["frames"]), int(arch["crop"])
    t = Tally(act_bytes=2)
    # the wire: packed I420, 1.5 bytes a pixel, converted on the device
    t.extra("wire", 0.0, frames * size * size * 1.5)
    h = out_len(size, 7, 2, 3)
    stem_mid, stem_out = arch["stem"]
    t.conv("stem.spatial", frames * size * size, frames * h * h, 49, 3,
           stem_mid)
    t.conv("stem.temporal", frames * h * h, frames * h * h, 3, stem_mid,
           stem_out)
    dims, cin = (frames, h, h), stem_out
    for si, (planes, blocks) in enumerate(zip(arch["stage_planes"],
                                              arch["stage_blocks"])):
        for bi in range(blocks):
            stride = 2 if si > 0 and bi == 0 else 1
            name = f"layer{si + 1}.{bi}"
            before = dims
            dims = conv2plus1d(t, f"{name}.conv1", dims, cin, planes, stride)
            dims = conv2plus1d(t, f"{name}.conv2", dims, planes, planes, 1)
            if stride != 1 or cin != planes:
                t.conv(f"{name}.downsample", before[0] * before[1] * before[2],
                       dims[0] * dims[1] * dims[2], 1, cin, planes)
            cin = planes
    t.extra("features", 0.0, arch["feature_dim"] * 4)
    batch = int(config["run_keys"][config["batch_key"]])
    return {**t.per_unit(batch), "layers": t.layers}
