"""What a resident group of deepseek-v2-lite-l7 holds: ``(rows, 2, 16384)
int32``, token ids and segment ids, as ``parallel/packer.py SegmentPacker``
lays a row out.

Every row is one full window of a document longer than the window: segment
id 1 throughout and no padding, which is what ``traffic/
resident-window-16k.json`` states under ``documents``. The lengths are the
same under every seed (:func:`lengths`, which ``costs/`` reads for the pairs
the attention has to compute); the seed draws every token (Zipf(1.0) over
the whole vocabulary, ``corpora/tokens.py``) and nothing else, so runs
differ only by which rare tokens they drew.
"""
import json
from pathlib import Path

import numpy as np
from vftbench import manifest

BENCH = Path(__file__).resolve().parents[1]
#: the configuration of this file's name: its vocabulary
CONFIG = json.loads(
    (BENCH / "configs" / f"{Path(__file__).stem}.json").read_text())
zipf_ids = manifest.load_module(BENCH / "corpora" / "tokens.py").zipf_ids
ZIPF_S = 1.0


def lengths(rows, row_len):
    """The documents of every row of a group: one, as long as the row."""
    return [[int(row_len)] for _ in range(int(rows))]


def resident_batch(rng, shape, dtype):
    rows, two, row_len = shape
    assert two == 2 and np.dtype(dtype) == np.int32, (shape, dtype)
    batch = np.zeros(shape, np.int32)
    for r, held in enumerate(lengths(rows, row_len)):
        at = 0
        for s, size in enumerate(held):
            batch[r, 0, at:at + size] = zipf_ids(
                rng, size, CONFIG["vocab_size"], ZIPF_S)
            batch[r, 1, at:at + size] = s + 1
            at += size
    return batch
