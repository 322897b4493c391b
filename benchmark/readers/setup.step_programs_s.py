"""Wall seconds of the set-up spent tracing, lowering and compiling or loading
the runners' jitted steps (the ledger's records named ``vft_*``; a union of
intervals, so a ``jit`` traced inside the step counts once). Tracing and
lowering are paid on a warm cache too."""
from vftbench import startup


def read(m):
    return startup.programs_s(m, steps=True)
