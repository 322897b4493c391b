"""LFM2-8B-A1B, the first pipeline stage: operations and bytes one packed
row of ``unit.window`` tokens needs.

Walks ``configs/lfm2-8b-a1b-l12.json`` (the published ``config.json`` keys
at its top level). One multiply-accumulate is two operations; every stage
reads its input and writes its output once in the serving type (2 bytes),
the weights are read once a dispatch. What runs on every position of the
row (the projections, the taps, the dense units) is counted over the row's
``T``; what the row's documents decide is counted from the row ``inputs/``
draws:

- A conv layer (``conv``): ``in_proj`` to 3 x D, the gates ``B * x`` and
  ``C * y`` and the ``conv_L_cache`` depthwise taps, ``out_proj``.
- An attention layer (``full_attention``): q, k, v, o on every token, and
  the core: per query head the scores and the mixing over the head's width
  of every causal, same-document (query, key) pair OF THE ROW: ``sum L (L +
  1) / 2`` over its documents' lengths, not ``T^2``, so that an attention
  that skips the pairs the mask throws away cannot read above its roofline.
- The dense layers (``num_dense_layers``): a gated unit
  ``intermediate_size`` wide.
- A routed layer: the router over all ``num_experts``; the experts at
  ``num_experts_per_tok`` assignments a DOCUMENT token (padding is routed
  nowhere; every expert is held here).

``kernels.moe_experts`` is the two grouped products of the routed experts,
all routed layers, with the experts' weights read once a dispatch.
"""
from pathlib import Path

from vftbench import manifest
from vftbench.shapes import Tally

ACT = 2  # bytes of an activation in the serving type
INPUTS = Path(__file__).resolve().parents[1] / "inputs" \
    / Path(__file__).name


def documents(config):
    """The lengths of the documents ``inputs/<config>.py lengths`` puts in
    one row."""
    (row,) = manifest.load_module(INPUTS).lengths(
        1, int(config["unit"]["window"]))
    return row


def causal_pairs(config):
    """The (query, key) pairs a row's attention has to compute: within each
    of its documents, the keys at or before the query."""
    return sum(size * (size + 1) // 2 for size in documents(config))


def per_unit(config):
    t = int(config["unit"]["window"])
    batch = int(config["run_keys"][config["batch_key"]])
    d = int(config["hidden_size"])
    taps = int(config["conv_L_cache"])
    heads, kv = (int(config["num_attention_heads"]),
                 int(config["num_key_value_heads"]))
    hd = d // heads
    dense, inner = (int(config["intermediate_size"]),
                    int(config["moe_intermediate_size"]))
    wide, top = int(config["num_experts"]), int(config["num_experts_per_tok"])
    first = int(config["num_dense_layers"])
    pairs = causal_pairs(config)
    tokens = sum(documents(config))

    tally = Tally(act_bytes=ACT)
    experts = {"flops": 0.0, "bytes": 0.0}
    for layer, kind in enumerate(config["layer_types"]):
        tally.weights += 2 * d                      # the two norms
        if kind == "conv":
            tally.conv("conv.in_proj", t, t, 1, d, 3 * d)
            # B * x, the taps, C * y: read B, C, x and write y
            tally.extra("conv.taps", t * d * (2.0 * taps + 1.0),
                        4 * t * d * ACT)
            tally.weights += taps * d
            tally.conv("conv.out_proj", t, t, 1, d, d)
        else:
            for name, width in (("q", heads * hd), ("k", kv * hd),
                                ("v", kv * hd)):
                tally.conv(f"attn.{name}", t, t, 1, d, width)
            tally.conv("attn.o", t, t, 1, heads * hd, d)
            tally.weights += 2 * hd                 # the q and k norms
            tally.extra("attn.core", heads * pairs * 2.0 * 2 * hd,
                        t * (2 * heads * hd + 2 * kv * hd) * ACT)
        if layer < first:
            tally.conv("dense.in", t, t, 1, d, 2 * dense)
            tally.conv("dense.out", t, t, 1, dense, d)
            continue
        tally.conv("moe.router", t, t, 1, d, wide)
        tally.weights += wide                       # expert_bias
        flops = tokens * top * (2.0 * d * 2 * inner + 2.0 * inner * d)
        rows = tokens * top * (d + 2 * inner + inner + inner + d) * ACT
        tally.extra("moe.experts", flops, rows)
        tally.weights += wide * 3 * d * inner
        experts["flops"] += flops
        experts["bytes"] += rows + wide * 3 * d * inner * ACT / batch
    tally.weights += int(config["vocab_size"]) * d + d  # embedding, last norm
    tally.extra("embed_and_pool", 2.0 * t * d, 2 * t * d * ACT)
    return {**tally.per_unit(batch, weight_bytes=ACT), "layers": tally.layers,
            # a position of the row: padding is routed nowhere
            "expected_assignments_a_token": float(top) * tokens / t,
            "kernels": {"moe_experts": experts}}
