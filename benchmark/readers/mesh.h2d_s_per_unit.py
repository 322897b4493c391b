"""StageProfiler ``h2d`` seconds inside the window per unit: the host-side
staging copy and enqueue of ``device_put``, a lower bound on wire time."""


def read(m):
    return m.per_unit(m.stage_s("h2d"))
