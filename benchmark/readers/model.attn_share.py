"""Share of the device's busy time in the traced sub-window spent in
operations under the program's ``GraniteHybrid/attn`` scope (self time by
``op_name``, ``vftbench/xspace.py``)."""
from vftbench import timeline


def read(m):
    return timeline.stage_share(m, "attn")
