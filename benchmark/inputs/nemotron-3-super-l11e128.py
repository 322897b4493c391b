"""What a resident group of nemotron-3-super-l11e128 holds: ``(rows, 2,
16384) int32``, token ids and segment ids, as ``parallel/packer.py
SegmentPacker`` lays a row out (segments 1, 2, ... one after the other, 0 is
padding at the row's end).

Every row packs 8 documents whose lengths are the mid-quantiles of
lognormal(median ``7 / 64`` of the row, sigma 0.5): for a row of 16,384
tokens 832 / 1,150 / 1,403 / 1,656 / 1,939 / 2,288 / 2,792 / 3,859, 15,919
of its positions, which is what ``traffic/resident-packed-16k-8doc.json``
states under ``documents``; the other 465 are padding. The lengths are the
same under every seed (:func:`lengths`, which ``costs/`` reads for the pairs
the attention has to compute and the tokens the experts see); the seed
draws the order of the documents in each row and every token (Zipf(1.0)
over the held vocabulary, ``corpora/tokens.py``), so runs differ by where
the boundaries fall and by which rare tokens they drew.
"""
import json
from pathlib import Path

import numpy as np
from vftbench import corpus, manifest

BENCH = Path(__file__).resolve().parents[1]
#: the configuration of this file's name: its held vocabulary
CONFIG = json.loads(
    (BENCH / "configs" / f"{Path(__file__).stem}.json").read_text())
zipf_ids = manifest.load_module(BENCH / "corpora" / "tokens.py").zipf_ids
DOCUMENTS_A_ROW, MEDIAN_OF_ROW, SIGMA, ZIPF_S = 8, 7 / 64, 0.5, 1.0


def lengths(rows, row_len):
    """The documents of every row of a group, the same in every row."""
    dist = {"dist": "lognormal", "median": row_len * MEDIAN_OF_ROW,
            "sigma": SIGMA}
    row = [max(1, int(round(corpus.quantile(
        dist, (i + 0.5) / DOCUMENTS_A_ROW)))) for i in range(DOCUMENTS_A_ROW)]
    assert sum(row) <= row_len, (row, row_len)
    return [list(row) for _ in range(int(rows))]


def resident_batch(rng, shape, dtype):
    rows, two, row_len = shape
    assert two == 2 and np.dtype(dtype) == np.int32, (shape, dtype)
    batch = np.zeros(shape, np.int32)
    for r, held in enumerate(lengths(rows, row_len)):
        at = 0
        for s, i in enumerate(rng.permutation(len(held))):
            size = held[i]
            batch[r, 0, at:at + size] = zipf_ids(
                rng, size, CONFIG["vocab_size"], ZIPF_S)
            batch[r, 1, at:at + size] = s + 1
            at += size
    return batch
