"""Seconds inside the host transform (the program's ``decode.transform``
span: resize, crop, wire encoding) inside the window, all threads, per unit.
A family that decodes at the served geometry has no transform: where the
program's recorder saw frames read and no transform, that is 0."""
from vftbench import timeline


def read(m):
    t = timeline.host(m)
    if t is None or not t.named("decode.read"):
        return None
    return m.per_unit(t.seconds(("decode.transform",), m.t0, m.t1) or 0.0)
