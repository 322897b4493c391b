"""Wall seconds of the set-up spent tracing, lowering and compiling or loading
every program that is no runner's step: flax's eager init, the seeded draws,
casts, the harness's own; the union of all the ledger's records less the part
inside a step's interval."""
from vftbench import startup


def read(m):
    return startup.programs_s(m, steps=False)
