"""Of the worker-thread seconds inside ``serve.request`` (clipped to the
window), the percent that no span below the umbrellas (``serve.request``,
``video_attempt``) covers: time of a request the program's timeline cannot
name."""
from vftbench import timeline


def read(m):
    t = timeline.host(m)
    return None if t is None else t.unnamed_share(m.t0, m.t1)
