"""Percent of the dispatches inside the window that were the packer's
ragged flush (every open video closing, nobody left to fill the group): the
program's ``packer.ragged_flush`` counter over its ``mesh.enqueue`` spans."""
from vftbench import timeline


def read(m):
    t = timeline.host(m)
    if t is None or not t.named("packer.stack"):
        return None
    inside = [d for d in t.dispatches() if m.t0 <= d["at"] < m.t1]
    if not inside:
        return None
    flushes = sum(1 for name, at, _ in t.counters
                  if name == "packer.ragged_flush" and m.t0 <= at < m.t1)
    return 100.0 * flushes / len(inside)
