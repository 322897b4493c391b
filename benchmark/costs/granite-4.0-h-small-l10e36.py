"""granite-4.0-h-small, one period, this chip's share: operations and bytes
one packed row of ``unit.window`` tokens needs.

Walks ``configs/granite-4.0-h-small-l10e36.json`` (the published
``config.json`` keys at its top level). One multiply-accumulate is two
operations; every stage reads its input and writes its output once in the
serving type (2 bytes), the weights are read once a dispatch.

- A Mamba-2 layer: ``in_proj`` and ``out_proj``, the depthwise convolution,
  and the scan in its chunked form with the causal half of a chunk: per
  token the (C.B) scores over half a chunk (one group for all heads), then
  per head the mixing over half a chunk, the chunk's state (P x N) and the
  carried state's read-out (P x N).
- The attention layer: q, k, v, o and the scores and mixing over the causal
  half of the row. Documents packed in a row see less than that (only their
  own tokens); the count is the row's, an upper bound that moves the whole
  step by under 2%.
- An expert layer: the router over all ``published.num_local_experts``; the
  held experts at the EXPECTED ``num_experts_per_tok x held / published``
  assignments a token (5, not 10: the others are the other chip's; the
  check prints the measured share beside it); the shared expert on every
  token.

``kernels.ssd_scan`` is the scan alone (scope ``GraniteHybrid/mamba/ssd``),
``kernels.moe_experts`` the two grouped products of the held experts, all
layers, with the held experts' weights read once a dispatch.
"""
from vftbench.shapes import Tally

ACT = 2  # bytes of an activation in the serving type


def per_unit(config):
    t = int(config["unit"]["window"])
    batch = int(config["run_keys"][config["batch_key"]])
    d = int(config["hidden_size"])
    kinds = list(config["layer_types"])
    # -- Mamba-2
    h, p, n = (int(config["mamba_n_heads"]), int(config["mamba_d_head"]),
               int(config["mamba_d_state"]))
    q, k = int(config["mamba_chunk_size"]), int(config["mamba_d_conv"])
    d_in = h * p
    conv = d_in + 2 * int(config["mamba_n_groups"]) * n
    proj = 2 * d_in + 2 * int(config["mamba_n_groups"]) * n + h
    # -- attention
    heads, kv = (int(config["num_attention_heads"]),
                 int(config["num_key_value_heads"]))
    hd = d // heads
    # -- experts
    wide = int(config["published"]["num_local_experts"])
    held = int(config["num_local_experts"])
    top = int(config["num_experts_per_tok"])
    i, s = (int(config["intermediate_size"]),
            int(config["shared_intermediate_size"]))
    here = top * held / wide          # expected assignments a token, here

    tally = Tally(act_bytes=ACT)
    scan = {"flops": 0.0, "bytes": 0.0}
    experts = {"flops": 0.0, "bytes": 0.0}
    for kind in kinds:
        if kind == "mamba":
            tally.conv("mamba.in_proj", t, t, 1, d, proj)
            tally.extra("mamba.conv", 2.0 * t * k * conv, 2 * t * conv * ACT)
            flops = t * (2.0 * n * q / 2                # C.B over half a chunk
                         + h * (2.0 * p * q / 2         # mixing
                                + 2.0 * p * n           # the chunk's state
                                + 2.0 * p * n))         # the carried state
            nbytes = t * (d_in * ACT + 2 * n * ACT + h * 4 + d_in * ACT)
            tally.extra("mamba.ssd", flops, nbytes)
            scan["flops"] += flops
            scan["bytes"] += nbytes
            tally.conv("mamba.out_proj", t, t, 1, d_in, d)
        else:
            for name, width in (("q", d), ("k", kv * hd), ("v", kv * hd),
                                ("o", d)):
                tally.conv(f"attn.{name}", t, t, 1, d, width)
            tally.extra("attn.core", heads * 2 * 2.0 * hd * t * (t + 1) / 2,
                        t * (2 * d + 2 * kv * hd) * ACT)
        tally.conv("moe.router", t, t, 1, d, wide)
        flops = t * here * (2.0 * d * 2 * i + 2.0 * i * d)
        rows = t * here * (d + 2 * i + i + i + d) * ACT
        tally.extra("moe.experts", flops, rows)
        tally.weights += held * 3 * d * i
        experts["flops"] += flops
        experts["bytes"] += rows + held * 3 * d * i * ACT / batch
        tally.conv("moe.shared_in", t, t, 1, d, 2 * s)
        tally.conv("moe.shared_out", t, t, 1, s, d)
    tally.weights += int(config["vocab_size"]) * d      # the held embedding
    tally.extra("embed_and_pool", 2.0 * t * d, 2 * t * d * ACT)
    return {**tally.per_unit(batch, weight_bytes=ACT), "layers": tally.layers,
            "expected_assignments_a_token": here,
            "kernels": {"ssd_scan": scan, "moe_experts": experts}}
