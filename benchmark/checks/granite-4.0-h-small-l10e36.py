"""What makes a granite-4.0-h-small-l10e36 result correct.

``validate``: a response's features are (windows, hidden) float32, finite
and not the same for every window; beside them ``expert_tokens`` (windows,
layers, 72) whose every layer counts ``num_experts_per_tok`` experts a token.

``compare``: the timed bfloat16 features of the check item (one document of
16,384 tokens: four full windows, the timed shape) against
``references/granite-4.0-h-small-l10e36.py`` (float32, precision "highest",
unrounded weights), window by window, by cosine and relative error, and the
two routers by the share of (token, layer, choice) assignments that went to
another expert (half the L1 distance of the count tables over their sum).
It also reports what ``costs/`` assumes of the routing: the share of
assignments that fell to the experts held here (expected 0.5) and the
fullest held expert's load over the mean, each with a limit that says
"the traffic is not what the cost model describes".

The first three limits lie between two readings on the chip (my chip runs,
PR 28, ``benchmark/control.py``; the check item and the weights are the same
under every seed, so every run reads the same). Lower, the program against
the reference: cosine 0.9999943649, relative error 0.0033577, 0.26984% of
the assignments moved. Upper, the reference's ``control`` (matrices rounded
to float8 e4m3) in the program's place: 0.9984185, 0.0564257, 1.18646%. The
limits: one minus the cosine at 1e-4 (18 times the lower reading's 5.6e-6, a
sixteenth of the upper's 1.58e-3); the error at 0.012 (3.6 times the lower,
under a quarter of the upper); the moved share at 0.6% (2.2 times the lower,
half the upper). The last two limits are not of precision (both readings
route the same tokens: 0.0096% and 0.025% off a half, 2.36 and 2.35 times
the mean): they say when the traffic stopped being what ``costs/`` counts.
"""
import json
from pathlib import Path

import numpy as np

#: the configuration of this file's name
CONFIG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                     / f"{Path(__file__).stem}.json").read_text())
FEATURE_DIM = CONFIG["hidden_size"]
LAYERS, TOP_K = CONFIG["num_hidden_layers"], CONFIG["num_experts_per_tok"]
EXPERTS = CONFIG["published"]["num_local_experts"]
HELD = CONFIG["num_local_experts"]
MIN_COSINE = 0.9999
MAX_RELATIVE_ERROR = 0.012
MAX_ROUTING_MOVED_SHARE = 0.006
MAX_LOCAL_SHARE_OFF = 0.05
MAX_EXPERT_LOAD_OVER_MEAN = 4.0


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
    if counts is None or counts.shape != (units, LAYERS, EXPERTS):
        return (f"expert_tokens {getattr(counts, 'shape', None)}, expected "
                f"{(units, LAYERS, EXPERTS)}")
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
    moved = float(np.abs(ran - ref).sum() / 2.0 / max(ref.sum(), 1.0))
    local = ran[..., :HELD]
    local_share = float(local.sum() / max(ran.sum(), 1.0))
    per_expert = local.sum(axis=0)                  # (layers, held)
    load = float((per_expert / per_expert.mean(axis=1, keepdims=True)).max())
    numbers = {"cosine_min": float(cosine.min()),
               "relative_error_max": float(relative.max()),
               "routing_moved_share": moved,
               "local_assignment_share_off": abs(local_share - HELD / EXPERTS),
               "largest_expert_load_over_mean": load}
    bands = {"cosine_min": MIN_COSINE,
             "relative_error_max": MAX_RELATIVE_ERROR,
             "routing_moved_share": MAX_ROUTING_MOVED_SHARE,
             "local_assignment_share_off": MAX_LOCAL_SHARE_OFF,
             "largest_expert_load_over_mean": MAX_EXPERT_LOAD_OVER_MEAN}
    ok = numbers["cosine_min"] >= MIN_COSINE and all(
        numbers[name] <= limit for name, limit in bands.items()
        if name != "cosine_min")
    return {"ok": bool(ok), "windows": int(a.shape[0]),
            "local_assignment_share": local_share, **numbers, "bands": bands}
