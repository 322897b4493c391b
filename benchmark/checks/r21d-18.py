"""What makes an r21d-18 result correct.

``validate``: a response's features are (clips, 512), finite and not the same
for every clip. ``compare``: the served bfloat16 features of the check video
against the float32 model on the same seeded weights, clip by clip.

The bands are set from the chip (my chip run, PR 22): bfloat16 against float32
measured a smallest cosine of 0.9999970 and a largest relative error of
0.00246 over the 16 clips of the check video, the same in every run. One bit
less of mantissa doubles the relative error and quadruples one minus the
cosine, so the bands sit at twice and four times what was measured: a type
below bfloat16 falls outside them, and a bfloat16 program whose rounding
falls a little differently stays inside.
"""
import numpy as np

FEATURE_DIM = 512
MIN_COSINE = 0.999988
MAX_RELATIVE_ERROR = 0.005


def validate(feats, key, units):
    x = feats.get(key)
    if x is None:
        return f"no {key!r} among {sorted(feats)}"
    if x.shape != (units, FEATURE_DIM):
        return f"shape {x.shape}, expected {(units, FEATURE_DIM)}"
    if not np.isfinite(x).all():
        return "non-finite features"
    if units > 1 and float(np.abs(x - x[0]).max()) == 0.0:
        return "the same features for every clip"
    return None


def compare(candidate, reference, key):
    a = np.asarray(candidate[key], np.float64)
    b = np.asarray(reference[key], np.float64)
    if a.shape != b.shape:
        return {"ok": False, "why": f"shapes {a.shape} and {b.shape}"}
    norm_a, norm_b = np.linalg.norm(a, axis=1), np.linalg.norm(b, axis=1)
    cosine = (a * b).sum(axis=1) / np.maximum(norm_a * norm_b, 1e-30)
    relative = np.linalg.norm(a - b, axis=1) / np.maximum(norm_b, 1e-30)
    return {"ok": bool(cosine.min() >= MIN_COSINE
                       and relative.max() <= MAX_RELATIVE_ERROR),
            "clips": int(a.shape[0]),
            "cosine_min": float(cosine.min()),
            "relative_error_max": float(relative.max()),
            "bands": {"cosine_min": MIN_COSINE,
                      "relative_error_max": MAX_RELATIVE_ERROR}}
