"""Width of the bracket on the offset between the host's clock and the
trace's, in milliseconds: tied by the work itself where the program's
``mesh.enqueue`` / ``mesh.fetch`` spans and the trace's module events allow
(``timeline.clock_bracket``), else the length of the ``start_trace`` call."""
from vftbench import timeline


def read(m):
    found = timeline.analysis(m)
    device = found and found["device"]
    if not device or "clock_bound_s" not in device:
        return None
    return 1e3 * device["clock_bound_s"]
