"""Share of the device's busy time in the traced sub-window spent in
operations that name no stage of the program (self time by
``op_name``, ``vftbench/xspace.py``)."""
from vftbench import timeline


def read(m):
    return timeline.stage_share(m, "unscoped")
