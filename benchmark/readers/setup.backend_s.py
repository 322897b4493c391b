"""Wall seconds of the set-up inside the program's ``startup.backend`` phase:
the extractor's first touch of the backend. Where the harness has touched it
first (``device.require_chip``), the runtime's start is the harness's and
this reads what is left."""
from vftbench import startup


def read(m):
    return startup.phases_s(m, "backend")
