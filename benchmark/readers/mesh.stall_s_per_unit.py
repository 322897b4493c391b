"""StageProfiler ``forward`` seconds inside the window per unit: the time
host threads stood still waiting for a result of the device. Not device
time."""


def read(m):
    return m.per_unit(m.stage_s("forward"))
