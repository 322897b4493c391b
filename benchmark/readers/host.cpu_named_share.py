"""Percent of the process's CPU seconds over the window (``os.times()`` at
both edges) that the program's working spans account for: the sum of their
self ``cpu`` (``time.thread_time()`` over each span, children on the same
thread taken out). The rest burns in threads no span runs on: the runtime's
transfer threads, cv2's decoder threads."""
from vftbench import timeline


def read(m):
    t = timeline.host(m)
    if t is None or not m.cpu_s:
        return None
    named = t.cpu_named(m.t0, m.t1)
    return None if named is None else 100.0 * named / m.cpu_s
