"""Share of the rows dispatched inside the window that were padding, from
the program's own count: ``rows`` and ``padded_rows`` on every
``mesh.enqueue`` span, a dispatch counted where it entered the runner.
``mesh.padding_share`` reads the same from the harness's wrapper."""
from vftbench import timeline


def read(m):
    found = timeline.analysis(m)
    if found is None:
        return None
    inside = [d for d in found["dispatches"] if m.t0 <= d["at"] < m.t1]
    padded = sum(d["padded_rows"] for d in inside)
    if not padded:
        return None
    return 100.0 * (1.0 - sum(d["rows"] for d in inside) / padded)
