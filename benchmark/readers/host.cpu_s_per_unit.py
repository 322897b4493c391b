"""User plus system CPU seconds of the whole process over the window
(``os.times()`` at both edges) per unit completed in it."""


def read(m):
    return m.per_unit(m.cpu_s)
