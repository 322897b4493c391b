"""Units per second of the model step alone: the median timing block of the
resident driver, each block about a second of back-to-back dispatches into
the extractor's own runner, fenced by one host read of its last output
(harness clock). Nothing is decoded, transferred or written inside a block."""
from vftbench import stats


def read(m):
    return stats.median(m.block_rates) if m.block_rates else None
