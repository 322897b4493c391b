"""Wall seconds of the set-up inside the program's ``startup.params``
(``resolve_params``: checkpoint load and conversion or the seeded init;
``init_params``: the token families' draw on the device) and ``startup.place``
(the host side of the parameters' ``device_put``) phases, as a union."""
from vftbench import startup


def read(m):
    return startup.phases_s(m, "params", "place")
