"""Percent of ``setup_s`` that no phase and no record of the program's
start-up ledger covers: the interpreter and the imports before the first
phase, the harness's corpus, calibration and ramp, and the device's own time
in the warm-up dispatches."""
from vftbench import startup


def read(m):
    return startup.unnamed_share(m)
