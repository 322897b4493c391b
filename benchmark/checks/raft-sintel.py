"""What makes a raft-sintel result correct.

``validate``: a response's flow is (pairs, 2, 240, 320), finite and not the
same for every pair. ``compare``: the served bfloat16 flow of the check video
against the float32 model on the same seeded weights, by end-point error per
pixel.

The bands are set from the chip (my chip run, PR 22): over the 16 pairs of the
check video bfloat16 against float32 measured a mean end-point error of
0.377 px and a 99th percentile of 1.198 px (largest 2.02 px), on flow whose
mean magnitude is 77.5 px because the weights are random. The bands sit at
twice what was measured: one bit less of mantissa doubles the error, so a type
below bfloat16 falls outside them. PR 19's single 2.0 px band on the largest
error would have let that through.
"""
import numpy as np

MAX_MEAN_EPE_PX = 0.75
MAX_P99_EPE_PX = 2.4


def validate(feats, key, units):
    x = feats.get(key)
    if x is None:
        return f"no {key!r} among {sorted(feats)}"
    if x.ndim != 4 or x.shape[:2] != (units, 2):
        return f"shape {x.shape}, expected ({units}, 2, H, W)"
    if not np.isfinite(x).all():
        return "non-finite flow"
    if units > 1 and float(np.abs(x - x[0]).max()) == 0.0:
        return "the same flow for every pair"
    if len(feats.get("timestamps_ms", ())) != units + 1:
        return (f"{len(feats.get('timestamps_ms', ()))} timestamps for "
                f"{units} pairs")
    return None


def compare(candidate, reference, key):
    a = np.asarray(candidate[key], np.float64)
    b = np.asarray(reference[key], np.float64)
    if a.shape != b.shape:
        return {"ok": False, "why": f"shapes {a.shape} and {b.shape}"}
    epe = np.sqrt(((a - b) ** 2).sum(axis=1))  # (pairs, H, W)
    mean, p99 = float(epe.mean()), float(np.percentile(epe, 99))
    magnitude = float(np.sqrt((b ** 2).sum(axis=1)).mean())
    return {"ok": bool(mean <= MAX_MEAN_EPE_PX and p99 <= MAX_P99_EPE_PX),
            "pairs": int(a.shape[0]), "mean_epe_px": mean, "p99_epe_px": p99,
            "max_epe_px": float(epe.max()),
            "reference_mean_flow_px": magnitude,
            "bands": {"mean_epe_px": MAX_MEAN_EPE_PX,
                      "p99_epe_px": MAX_P99_EPE_PX}}
