"""StageProfiler ``decode`` seconds inside the window, all threads, per unit
completed in it: cv2 read plus the host transform."""


def read(m):
    return m.per_unit(m.stage_s("decode"))
