"""NVIDIA-Nemotron-3-Super, stage 0, rank 0 of 4: operations and bytes one
packed row of ``unit.window`` tokens needs.

Walks ``configs/nemotron-3-super-l11e128.json`` (the published
``config.json`` keys at its top level). One multiply-accumulate is two
operations; every stage reads its input and writes its output once in the
serving type (2 bytes), the weights are read once a dispatch. What runs on
every position of the row is counted over the row's ``T``; what the row's
documents decide is counted from the row ``inputs/`` draws:

- An ``M`` layer: ``in_proj`` to ``[z | x B C | dt]``, the depthwise
  convolution, the scan in its chunked form with the causal half of a chunk
  (per token the (C.B) scores over half a chunk once a group, then per head
  the mixing over half a chunk, the chunk's state (P x N) and the carried
  state's read-out (P x N)), the gated group norm, ``out_proj``.
- The ``*`` layer: q, k, v, o on every token, and the core: per query head
  the scores and the mixing of every causal, same-document (query, key)
  pair OF THE ROW, ``sum L (L + 1) / 2`` over its documents' lengths.
- An ``E`` layer: the router over all ``published.n_routed_experts``, the two
  latent projections and the shared unit on every token; the held experts
  at ``measured.held_share`` of a document token's ``num_experts_per_tok``
  assignments (padding is routed nowhere).

``kernels.moe_experts`` is the two grouped products of the held experts,
all E layers, with the held experts' weights read once a dispatch. The
count takes the held share the seeded router gives, not the uniform
``held / published`` (0.25): a router that sends more than a quarter of its
assignments here would otherwise make the kernels' share of their roofline
read over 100%.
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
    # -- M
    h, p, n = (int(config["mamba_num_heads"]), int(config["mamba_head_dim"]),
               int(config["ssm_state_size"]))
    g, q, taps = (int(config["n_groups"]), int(config["chunk_size"]),
                  int(config["conv_kernel"]))
    d_in = h * p
    conv = d_in + 2 * g * n
    # -- *
    heads, kv, hd = (int(config["num_attention_heads"]),
                     int(config["num_key_value_heads"]),
                     int(config["head_dim"]))
    pairs = causal_pairs(config)
    # -- E
    wide = int(config["published"]["n_routed_experts"])
    held = int(config["n_routed_experts"])
    top = int(config["num_experts_per_tok"])
    i, lat, s = (int(config["moe_intermediate_size"]),
                 int(config["moe_latent_size"]),
                 int(config["moe_shared_expert_intermediate_size"]))
    tokens = sum(documents(config))
    # held assignments a document token: the configuration's measured share
    here = top * float(config["measured"]["held_share"])

    tally = Tally(act_bytes=ACT)
    experts = {"flops": 0.0, "bytes": 0.0}
    for kind in config["hybrid_override_pattern"]:
        tally.weights += d                          # the block's norm
        if kind == "M":
            tally.conv("mamba.in_proj", t, t, 1, d, d_in + conv + h)
            tally.extra("mamba.conv", 2.0 * t * taps * conv,
                        2 * t * conv * ACT)
            tally.weights += taps * conv + conv + 3 * h + d_in
            tally.extra("mamba.ssd", t * (g * 2.0 * n * q / 2
                                          + h * (2.0 * p * q / 2
                                                 + 2.0 * p * n
                                                 + 2.0 * p * n)),
                        t * (d_in * ACT + 2 * g * n * ACT + h * 4
                             + d_in * ACT))
            tally.extra("mamba.norm", 4.0 * t * d_in, 3 * t * d_in * ACT)
            tally.conv("mamba.out_proj", t, t, 1, d_in, d)
        elif kind == "*":
            for name, width in (("q", heads * hd), ("k", kv * hd),
                                ("v", kv * hd)):
                tally.conv(f"attn.{name}", t, t, 1, d, width)
            tally.conv("attn.o", t, t, 1, heads * hd, d)
            tally.extra("attn.core", heads * pairs * 2.0 * 2 * hd,
                        t * (2 * heads * hd + 2 * kv * hd) * ACT)
        else:
            tally.conv("moe.router", t, t, 1, d, wide)
            tally.weights += wide                   # the selection bias
            tally.conv("moe.latent_down", t, t, 1, d, lat)
            flops = tokens * here * (2.0 * lat * i + 2.0 * i * lat)
            rows = tokens * here * (lat + i + i + lat) * ACT
            tally.extra("moe.experts", flops, rows)
            tally.weights += held * 2 * lat * i
            experts["flops"] += flops
            experts["bytes"] += rows + held * 2 * lat * i * ACT / batch
            tally.conv("moe.latent_up", t, t, 1, lat, d)
            tally.conv("moe.shared_in", t, t, 1, d, s)
            tally.conv("moe.shared_out", t, t, 1, s, d)
    tally.weights += int(config["vocab_size"]) * d + d  # embedding, last norm
    tally.extra("embed_and_pool", 2.0 * t * d, 2 * t * d * ACT)
    return {**tally.per_unit(batch, weight_bytes=ACT), "layers": tally.layers,
            # a position of the row: padding is routed nowhere
            "expected_assignments_a_token": here * tokens / t,
            "kernels": {"moe_experts": experts}}
