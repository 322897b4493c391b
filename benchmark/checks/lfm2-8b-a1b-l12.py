"""What makes an lfm2-8b-a1b-l12 result correct.

``validate``: a response's features are (windows, hidden) float32, finite
and not the same for every window; beside them ``expert_tokens`` (windows,
10 routed layers, 32) whose every layer counts ``num_experts_per_tok``
experts a token.

``compare``: the timed bfloat16 features of the check item (one document of
16,384 tokens: one full window, the timed shape, with no segment boundary
in it) against
``references/lfm2-8b-a1b-l12.py`` (float32, precision "highest", unrounded
weights) by cosine and relative error, and the two routers by the share of
(token, layer, choice) assignments that went to another expert (half the L1
distance of the count tables over their sum). It also reports what
``costs/`` assumes of the routing, the fullest expert's load over the mean,
with a limit that says "the traffic is not what the cost model describes".

What the comparison cannot see: the check item is one document, so no tap
and no attention block of it reads across a boundary between documents,
and the reference computes no segment mask. A program that leaked across a
boundary of the timed packed rows would still be ``correct`` here. The
boundaries are held by ``tests/test_lfm2_moe.py`` alone (packed rows of
several documents against the reference, the taps across a boundary), on
the CPU at small widths.

The first two limits lie between two readings on a TPU v5 lite
(``benchmark/control.py``; the check item and the weights are the
same under every seed, so every run reads the same). Lower, the program
against the reference: cosine 0.9995962, relative error 0.0284994, 0.26886%
of the assignments moved. Upper, the reference's ``control`` (matrices
rounded to float8 e4m3) in the program's place: 0.9900059, 0.1412935,
0.67322%. The limits: one minus the cosine at 2e-3 (4.9 times the lower
reading's 4.04e-4, a fifth of the upper's 9.99e-3); the error at 0.06 (2.1
times the lower, the upper 2.4 times it). The lower readings are wider than
dsv2's (0.99996, 0.0087) though the float8 control reads alike (0.9909,
0.135): the pooled feature is small beside the states it averages (the mean
over the window is 5% of a token's norm here), so what the program's
rounding does to each token, which averages out, weighs more in it; token
by token the states of the last layer part by 14% (bfloat16) where each
layer, handed the reference's input, adds 0.7-0.9% (the dense conv layers),
3.5-3.8% (the routed conv layers: near-tied choices of a sigmoid move) and
7% (attention) (a layer-by-layer probe on the same chip).

The moved share has no upper reading: the control moves 2.5 times the
assignments the program moves, less than three times, so the two readings
do not bound a limit between them. It is held as a guard alone, at 0.45%:
1.7 times the program's reading, which is the same under every seed since
the check item and the weights are, and two thirds of the control's. It
stops a router whose choices drift further from the reference's than the
program's float32 router does (logits rounded to bfloat16 swap the fourth
and fifth expert of 0.7% of the tokens in ``tests/test_lfm2_moe.py``, where
float32 logits swap none) even where the features stay inside their
limits. The control's 0.673% is over it too, but ``correct`` does not
rest on this limit to refuse the control: the first two refuse it.

The last limit is not of precision (both readings route the same tokens:
2.355 and 2.365 times the mean): it says when the traffic stopped being what
``costs/`` counts. The grouped products' operations do not depend on the
split, so the cost model holds while no layer's choices collapse onto the
same four experts (8.0 times the mean); the limit, 6.0, lies between.
"""
import json
from pathlib import Path

import numpy as np

#: the configuration of this file's name
CONFIG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                     / f"{Path(__file__).stem}.json").read_text())
FEATURE_DIM = CONFIG["hidden_size"]
TOP_K, EXPERTS = CONFIG["num_experts_per_tok"], CONFIG["num_experts"]
#: the layers behind the leading dense ones route
ROUTED_LAYERS = CONFIG["num_hidden_layers"] - CONFIG["num_dense_layers"]
MIN_COSINE = 0.998
MAX_RELATIVE_ERROR = 0.06
MAX_ROUTING_MOVED_SHARE = 0.0045
MAX_EXPERT_LOAD_OVER_MEAN = 6.0


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
    if counts is None or counts.shape != (units, ROUTED_LAYERS, EXPERTS):
        return (f"expert_tokens {getattr(counts, 'shape', None)}, expected "
                f"{(units, ROUTED_LAYERS, EXPERTS)}")
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
    per_expert = ran.sum(axis=0)                    # (routed layers, experts)
    load = float((per_expert / per_expert.mean(axis=1, keepdims=True)).max())
    numbers = {"cosine_min": float(cosine.min()),
               "relative_error_max": float(relative.max()),
               "routing_moved_share": moved,
               "largest_expert_load_over_mean": load}
    bands = {"cosine_min": MIN_COSINE,
             "relative_error_max": MAX_RELATIVE_ERROR,
             "routing_moved_share": MAX_ROUTING_MOVED_SHARE,
             "largest_expert_load_over_mean": MAX_EXPERT_LOAD_OVER_MEAN}
    ok = numbers["cosine_min"] >= MIN_COSINE and all(
        numbers[name] <= limit for name, limit in bands.items()
        if name != "cosine_min")
    return {"ok": bool(ok), "windows": int(a.shape[0]), **numbers,
            "bands": bands}
