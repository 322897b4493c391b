"""Programs the persistent compile cache did not hold, so the backend
compiled them, before the window opened (``/jax/compilation_cache/
cache_misses`` stamped by the start-up ledger): 0 on a warm run, and why a
set's first run reads minutes."""
from vftbench import startup


def read(m):
    snap = startup.snapshot(m)
    return None if snap is None else startup.count(snap, "miss")
