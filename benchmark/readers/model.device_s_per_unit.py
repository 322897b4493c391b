"""Device-busy seconds per unit: the busy share of the traced sub-window
(union of the operation intervals on the TPU plane over its length) over the
units per second dispatched to the runner in the whole window, less the
seconds in which the resident driver started and stopped the profiler."""


def read(m):
    return m.device_s_per_unit()
