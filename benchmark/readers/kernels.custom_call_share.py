"""Share of the device's busy time spent inside Mosaic custom calls (the
Pallas kernels), from the operations' self time in the traced sub-window."""
from vftbench.measurement import MOSAIC_OPS


def read(m):
    share = m.op_share(MOSAIC_OPS)
    return None if share is None else 100.0 * share
