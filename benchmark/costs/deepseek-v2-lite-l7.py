"""DeepSeek-V2-Lite, the first pipeline stage: operations and bytes one row
of ``unit.window`` tokens needs.

Walks ``configs/deepseek-v2-lite-l7.json`` (the published ``config.json``
keys at its top level). One multiply-accumulate is two operations; every
stage reads its input and writes its output once in the serving type (2
bytes), the weights are read once a dispatch.

- Attention, every layer: the projections ``q_proj``, ``kv_a_proj_with_mqa``,
  ``kv_b_proj`` and ``o_proj`` on every token, and the core: per head the
  scores over ``qk_nope_head_dim + qk_rope_head_dim`` and the mixing over
  ``v_head_dim`` of every causal, same-document (query, key) pair OF THE ROW
  ``inputs/`` DRAWS: ``sum L (L + 1) / 2`` over its documents' lengths, not
  ``T^2`` and no constant, so that an attention that skips the pairs the
  mask throws away cannot read above its roofline.
- The dense layers (``first_k_dense_replace``): a gated unit
  ``intermediate_size`` wide.
- An expert layer: the gate over all ``n_routed_experts``; the routed
  experts at ``num_experts_per_tok`` assignments a token (every expert is
  held here); the ``n_shared_experts`` shared ones on every token.

``kernels.mla_core`` is the core alone (scope ``DeepSeekV2/attn/core``),
all layers; ``kernels.moe_experts`` the two grouped products of the routed
experts, all layers, with the experts' weights read once a dispatch.
"""
from pathlib import Path

from vftbench import manifest
from vftbench.shapes import Tally

ACT = 2  # bytes of an activation in the serving type
INPUTS = Path(__file__).resolve().parents[1] / "inputs" \
    / Path(__file__).name


def causal_pairs(config):
    """The (query, key) pairs a row's attention has to compute: within each
    of the documents ``inputs/<config>.py lengths`` puts in a row, the keys
    at or before the query."""
    t = int(config["unit"]["window"])
    (row,) = manifest.load_module(INPUTS).lengths(1, t)
    return sum(size * (size + 1) // 2 for size in row)


def per_unit(config):
    t = int(config["unit"]["window"])
    batch = int(config["run_keys"][config["batch_key"]])
    d = int(config["hidden_size"])
    depth = int(config["num_hidden_layers"])
    heads = int(config["num_attention_heads"])
    nope, rope, v = (int(config["qk_nope_head_dim"]),
                     int(config["qk_rope_head_dim"]),
                     int(config["v_head_dim"]))
    rank = int(config["kv_lora_rank"])
    dense, inner = (int(config["intermediate_size"]),
                    int(config["moe_intermediate_size"]))
    wide, top, shared = (int(config["n_routed_experts"]),
                         int(config["num_experts_per_tok"]),
                         int(config["n_shared_experts"]))
    first, freq = (int(config["first_k_dense_replace"]),
                   int(config["moe_layer_freq"]))
    pairs = causal_pairs(config)

    tally = Tally(act_bytes=ACT)
    core = {"flops": 0.0, "bytes": 0.0, "pairs": pairs}
    experts = {"flops": 0.0, "bytes": 0.0}
    for layer in range(depth):
        tally.conv("attn.q", t, t, 1, d, heads * (nope + rope))
        tally.conv("attn.kv_a", t, t, 1, d, rank + rope)
        tally.conv("attn.kv_b", t, t, 1, rank, heads * (nope + v))
        tally.conv("attn.o", t, t, 1, heads * v, d)
        tally.weights += rank + 2 * d       # kv_a_layernorm, the two norms
        flops = heads * pairs * 2.0 * (nope + rope + v)
        nbytes = t * heads * (2 * (nope + rope) + 2 * v) * ACT
        tally.extra("attn.core", flops, nbytes)
        core["flops"] += flops
        core["bytes"] += nbytes
        if layer < first or layer % freq:
            tally.conv("dense.in", t, t, 1, d, 2 * dense)
            tally.conv("dense.out", t, t, 1, dense, d)
            continue
        tally.conv("moe.router", t, t, 1, d, wide)
        flops = t * top * (2.0 * d * 2 * inner + 2.0 * inner * d)
        rows = t * top * (d + 2 * inner + inner + inner + d) * ACT
        tally.extra("moe.experts", flops, rows)
        tally.weights += wide * 3 * d * inner
        experts["flops"] += flops
        experts["bytes"] += rows + wide * 3 * d * inner * ACT / batch
        tally.conv("moe.shared_in", t, t, 1, d, 2 * shared * inner)
        tally.conv("moe.shared_out", t, t, 1, shared * inner, d)
    tally.weights += int(config["vocab_size"]) * d + d  # embedding, last norm
    tally.extra("embed_and_pool", 2.0 * t * d, 2 * t * d * ACT)
    return {**tally.per_unit(batch, weight_bytes=ACT), "layers": tally.layers,
            "expected_assignments_a_token": float(top),
            "kernels": {"mla_core": core, "moe_experts": experts}}
