"""FLOPs and bytes of one 0.96 s VGGish example, from its shapes: 3x3
convolutions at 96x64, 48x32, 24x16 (two) and 12x8 (two), each stage followed
by a 2x2 pool, then three dense layers from the 6x4x512 map."""

CONVS = ((96, 64, 1, 64), (48, 32, 64, 128), (24, 16, 128, 256),
         (24, 16, 256, 256), (12, 8, 256, 512), (12, 8, 512, 512))
DENSE = ((6 * 4 * 512, 4096), (4096, 4096), (4096, 128))


def per_unit(config):
    flops = sum(2 * 9 * cin * cout * h * w for h, w, cin, cout in CONVS) \
        + sum(2 * a * b for a, b in DENSE)
    params = sum(9 * cin * cout + cout for _, _, cin, cout in CONVS) \
        + sum(a * b + b for a, b in DENSE)
    activations = 96 * 64 + sum(h * w * cout for h, w, _, cout in CONVS) \
        + sum(b for _, b in DENSE)
    batch = int(config["run_keys"][config["batch_key"]])
    # bfloat16: the weights are read once a batch, every activation is
    # written once and read once
    return {"flops": float(flops),
            "bytes": 2.0 * params / batch + 2.0 * 2.0 * activations}
