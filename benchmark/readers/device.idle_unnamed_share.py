"""Percent of the traced sub-window's idle seconds that lie in gaps no host
span explains: gaps shorter than four clock bounds (not attributed, by rule)
and gaps in which the thread that next enqueued was inside no span."""
from vftbench import timeline


def read(m):
    found = timeline.analysis(m)
    device = found and found["device"]
    if not device or not device.get("idle_s"):
        return None
    return 100.0 * device["idle_unnamed_s"] / device["idle_s"]
