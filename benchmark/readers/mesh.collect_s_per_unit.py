"""Host seconds spent between the device-to-host copy and the sink (the
program's ``packer.route`` and ``batch.collect`` spans: valid-row slicing,
transposes, the per-video concatenation) inside the window, per unit."""
from vftbench import timeline


def read(m):
    return timeline.span_s_per_unit(m, "packer.route", "batch.collect")
