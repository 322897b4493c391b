"""The ``tokens`` corpus kind: a file of token ids, raw little-endian int32,
what a tokenizer run over a caption or a transcript leaves. A frame is a
token; ``fps`` has no meaning beyond the plan's arithmetic (lengths are
``duration_s x fps`` tokens).

Ids are Zipf(``zipf_s``) over ``vocab`` ranks with id = rank - 1: the same
few ids are the frequent ones under every seed, as a tokenizer's are, so a
run's routing differs by which rare ids it drew, not by which ids are common.
"""
import numpy as np

SUFFIX = ".tokens"
#: what makes a fixed file what it is: key -> default (None: no default)
GEOMETRY = {"vocab": None, "zipf_s": 1.0}


def zipf_ids(rng, count, vocab, s=1.0):
    """``count`` ids in ``[0, vocab)``, P(id = r - 1) proportional to
    ``r ** -s``, by the inverse of the cumulative weights."""
    weights = np.arange(1, int(vocab) + 1, dtype=np.float64) ** -float(s)
    cumulative = np.cumsum(weights)
    draws = rng.random(int(count)) * cumulative[-1]
    return np.minimum(np.searchsorted(cumulative, draws),
                      int(vocab) - 1).astype(np.int32)


def write(path, frames, spec, rng):
    zipf_ids(rng, frames, spec["vocab"], spec.get("zipf_s", 1.0)
             ).astype("<i4").tofile(str(path))
