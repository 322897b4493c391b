"""Share of the traced sub-window in which no operation ran on the device:
one minus device-busy seconds over the sub-window's length."""


def read(m):
    return m.idle_share()
