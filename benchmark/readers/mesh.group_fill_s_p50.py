"""Median, over the requests the packer served inside the window, of the
seconds a worker waited in ``ClipPacker.close_video`` for other videos to
fill the shared group (the program's ``packer.fill_wait`` spans, summed per
request; a request that never waited counts as 0). Nothing to read where the
run did not pack (no ``packer.stack`` span)."""
from vftbench import stats, timeline


def read(m):
    t = timeline.host(m)
    if t is None:
        return None
    per_request = {s.rid: 0.0 for s in t.named("packer.stack")
                   if s.rid is not None and m.t0 <= s.start < m.t1}
    for s in t.named("packer.fill_wait"):
        if s.rid is not None and m.t0 <= s.start < m.t1:
            per_request[s.rid] = per_request.get(s.rid, 0.0) + s.dur
    return stats.median(list(per_request.values())) if per_request else None
