"""What makes a vggish-tiny result correct. ``validate``: (examples, 128),
finite, not the same for every example. ``compare``: the timed bfloat16
embeddings of the check input against a float32 reference, example by example.

The bands lie between two readings on the CPU the fixture runs on (PR 26,
over the check input's three examples; weights and check input are the same
under every seed, so every run reads the same). Lower: the bfloat16 program
against ``references/vggish-tiny.py`` on the loader's unrounded weights reads
a smallest cosine of 0.9999714 and a largest relative error of 0.0075804
(against the program's float32 twin 0.9999714 and 0.0075804: the plain
reference and the flax twin agree to seven digits). Upper: that file's
``control``, the reference in float8 in the program's place, reads 0.99169
and 0.12978. The limits, 0.99988 and 0.015, are four times one minus the
cosine and twice the error of the lower reading, a ninth of the upper. A
fixture's bands: a cell sets its own on the chip.
"""
import numpy as np

FEATURE_DIM = 128
MIN_COSINE = 0.99988
MAX_RELATIVE_ERROR = 0.015


def validate(feats, key, units):
    x = feats.get(key)
    if x is None:
        return f"no {key!r} among {sorted(feats)}"
    if x.shape != (units, FEATURE_DIM):
        return f"shape {x.shape}, expected {(units, FEATURE_DIM)}"
    if not np.isfinite(x).all():
        return "non-finite features"
    if units > 1 and float(np.abs(x - x[0]).max()) == 0.0:
        return "the same features for every example"
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
            "examples": int(a.shape[0]),
            "cosine_min": float(cosine.min()),
            "relative_error_max": float(relative.max()),
            "bands": {"cosine_min": MIN_COSINE,
                      "relative_error_max": MAX_RELATIVE_ERROR}}
