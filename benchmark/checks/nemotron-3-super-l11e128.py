"""What makes a nemotron-3-super-l11e128 result correct.

``validate``: a response's features are (windows, hidden) float32, finite
and not the same for every window; beside them ``expert_tokens`` (windows,
5 E layers, 512) whose every layer counts ``num_experts_per_tok`` experts a
token.

``compare``: the timed bfloat16 features of the check item (one document of
16,384 tokens: one full window, the timed shape, with no segment boundary
in it) against ``references/nemotron-3-super-l11e128.py`` (float32,
precision "highest", unrounded weights, the recurrence token by token) by
cosine and relative error, and the two routers by the share of (token,
layer, choice) assignments that went to another expert (half the L1
distance of the count tables over their sum). It also reports what
``costs/`` assumes of the routing: the share of the assignments the held
experts take, against the share ``costs/`` counts (``measured.held_share``
of the configuration), and
the fullest held expert's load over the held experts' mean, each with a
limit that says "the traffic is not what the cost model describes".

What the comparison cannot see: the check item is one document, so no
scan state, tap or attention block of it reads across a boundary between
documents, and the reference computes no segment mask. The boundaries are
held by ``tests/test_nemotron_h.py`` alone (packed rows of several
documents against the reference), on the CPU at small widths.

The first three limits lie between two readings on a TPU v5 lite
(``benchmark/control.py``; the check item and the weights are the same
under every seed, so every run reads the same). Lower, the program against
the reference: cosine 0.9999968, relative error 0.0025129, 0.24603% of the
assignments moved. Upper, the reference's ``control`` (matrices rounded to
float8 e4m3) in the program's place: 0.9987425, 0.0501463, 1.77657%. The
limits: one minus the cosine at 6e-5 (19 times the lower reading's 3.15e-6,
a twenty-first of the upper's 1.26e-3); the error at 0.011 (4.4 times the
lower, the upper 4.6 times it); the moved share at 0.7% (2.8 times the
lower, the upper 2.5 times it). The pooled feature agrees more closely
than lfm2's (0.028): the seeded relu2 experts and the Mamba blocks move a
token's state less than a gated unit and a QK-normed attention do.

The last two limits are not of precision (both readings route the same
tokens: held shares 0.2615 and 0.2606, loads 8.34 and 8.22 times the mean):
they say when the traffic stopped being what ``costs/`` counts. The held
share counted (0.26) is off the check item's by 0.0015; at 0.02 off the
grouped products' count would be 8% wrong, so the limit is 0.02. A held
expert takes at most every token, 22.4 times the mean of 732 assignments;
Zipf's frequent ids already make the fullest 8.3 times the mean; the limit,
12, lies between.
"""
import json
from pathlib import Path

import numpy as np

#: the configuration of this file's name
CONFIG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                     / f"{Path(__file__).stem}.json").read_text())
FEATURE_DIM = CONFIG["hidden_size"]
TOP_K = CONFIG["num_experts_per_tok"]
EXPERTS = CONFIG["published"]["n_routed_experts"]
HELD = CONFIG["n_routed_experts"]
E_LAYERS = CONFIG["hybrid_override_pattern"].count("E")
#: the held share ``costs/`` counts the grouped products at
HELD_SHARE = CONFIG["measured"]["held_share"]
MIN_COSINE = 0.99994
MAX_RELATIVE_ERROR = 0.011
MAX_ROUTING_MOVED_SHARE = 0.007
MAX_HELD_SHARE_OFF = 0.02
MAX_EXPERT_LOAD_OVER_MEAN = 12.0


def validate(feats, key, units):
    x = feats.get(key)
    if x is None:
        return f"no {key!r} among {sorted(feats)}"
    if x.shape != (units, FEATURE_DIM):
        return f"shape {x.shape}, expected {(units, FEATURE_DIM)}"
    if not np.isfinite(x).all():
        return "non-finite features"
    if units > 1 and float(np.abs(x - x[0]).max()) == 0.0:
        return "the same features for every window"
    counts = feats.get("expert_tokens")
    if counts is None or counts.shape != (units, E_LAYERS, EXPERTS):
        return (f"expert_tokens {getattr(counts, 'shape', None)}, expected "
                f"{(units, E_LAYERS, EXPERTS)}")
    per_layer = counts.sum(axis=2)
    if (per_layer % TOP_K).any() or (per_layer != per_layer[:, :1]).any():
        return "a layer that does not count top-k experts for every token"
    return None


def compare(candidate, reference, key):
    a = np.asarray(candidate[key], np.float64)
    b = np.asarray(reference[key], np.float64)
    if a.shape != b.shape:
        return {"ok": False, "why": f"shapes {a.shape} and {b.shape}"}
    norm_a, norm_b = np.linalg.norm(a, axis=1), np.linalg.norm(b, axis=1)
    cosine = (a * b).sum(axis=1) / np.maximum(norm_a * norm_b, 1e-30)
    relative = np.linalg.norm(a - b, axis=1) / np.maximum(norm_b, 1e-30)
    ran = np.asarray(candidate["expert_tokens"], np.float64)
    ref = np.asarray(reference["expert_tokens"], np.float64)
    if ran.shape != ref.shape:
        return {"ok": False, "why": f"expert_tokens {ran.shape} and "
                                    f"{ref.shape}"}
    moved = float(np.abs(ran - ref).sum() / 2.0 / max(ref.sum(), 1.0))
    local = ran[..., :HELD]
    held_share = float(local.sum() / max(ran.sum(), 1.0))
    per_expert = local.sum(axis=0)                  # (E layers, held)
    load = float((per_expert / per_expert.mean(axis=1, keepdims=True)).max())
    numbers = {"cosine_min": float(cosine.min()),
               "relative_error_max": float(relative.max()),
               "routing_moved_share": moved,
               "held_share_off": abs(held_share - HELD_SHARE),
               "largest_expert_load_over_mean": load}
    bands = {"cosine_min": MIN_COSINE,
             "relative_error_max": MAX_RELATIVE_ERROR,
             "routing_moved_share": MAX_ROUTING_MOVED_SHARE,
             "held_share_off": MAX_HELD_SHARE_OFF,
             "largest_expert_load_over_mean": MAX_EXPERT_LOAD_OVER_MEAN}
    ok = numbers["cosine_min"] >= MIN_COSINE and all(
        numbers[name] <= limit for name, limit in bands.items()
        if name != "cosine_min")
    return {"ok": bool(ok), "windows": int(a.shape[0]),
            "held_share": held_share,
            "held_share_by_layer": (local.sum(axis=(0, 2))
                                    / np.maximum(ran.sum(axis=(0, 2)), 1.0)
                                    ).tolist(),
            **numbers, "bands": bands}
