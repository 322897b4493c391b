"""What a resident group of granite-4.0-h-small-l10e36 holds: ``(rows, 2,
4096) int32``, token ids and segment ids, as ``parallel/packer.py
SegmentPacker`` lays a row out (segments 1, 2, ... one after the other, 0 is
padding at the row's end).

A group of ``rows`` rows of ``row_len`` tokens packs ``5 x rows`` documents
whose lengths are the mid-quantiles of lognormal(median ``row_len / 8``,
sigma 1.0). For the cell's (4, 2, 4096) that is what
``traffic/resident-packed-4k.json`` states under ``documents``: 20 documents
of 72 to 3,635 tokens (median 512), 16,093 of a group's 16,384 positions.
The lengths are the same under every seed and so is their division into rows
(largest first, each into the first row with room: 4,059 / 4,060 / 4,029 /
3,945 tokens); the seed says which document has which length, the order of
the documents in a row and of the rows, and draws every token (Zipf(1.0)
over the held vocabulary, ``corpora/tokens.py``). A step's work follows the
routing: with lengths and the ids' frequency order fixed, runs differ only
by which rare tokens they drew.
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
DOCUMENTS_A_ROW, MEDIAN_OF_ROW, SIGMA, ZIPF_S = 5, 1 / 8, 1.0, 1.0


def lengths(rows, row_len):
    n = DOCUMENTS_A_ROW * rows
    dist = {"dist": "lognormal", "median": row_len * MEDIAN_OF_ROW,
            "sigma": SIGMA}
    return [max(1, int(round(corpus.quantile(dist, (i + 0.5) / n))))
            for i in range(n)]


def rows_of(sizes, rows, row_len):
    """Largest first, each into the first row with room."""
    held = [[] for _ in range(rows)]
    for size in sorted(sizes, reverse=True):
        row = next((r for r in held if sum(r) + size <= row_len), None)
        if row is None:
            raise ValueError(f"{sizes} do not fit {rows} rows of {row_len}")
        row.append(size)
    return held


def resident_batch(rng, shape, dtype):
    rows, two, row_len = shape
    assert two == 2 and np.dtype(dtype) == np.int32, (shape, dtype)
    batch = np.zeros(shape, np.int32)
    held = rows_of(lengths(rows, row_len), rows, row_len)
    for r, at_row in enumerate(rng.permutation(rows)):
        at = 0
        for s, i in enumerate(rng.permutation(len(held[r]))):
            size = held[r][i]
            batch[at_row, 0, at:at + size] = zipf_ids(
                rng, size, CONFIG["vocab_size"], ZIPF_S)
            batch[at_row, 1, at:at + size] = s + 1
            at += size
    return batch
