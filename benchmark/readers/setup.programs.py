"""Programs compiled, or loaded from the persistent cache, before the window
opened: the start-up ledger's ``backend_compile_duration`` records that ended
by ``m.t0``. The video cells' flax ``model.init`` runs one program an
operation; the token cells draw their weights in one program a kind of
layer."""
from vftbench import startup


def read(m):
    snap = startup.snapshot(m)
    return None if snap is None else startup.count(snap, "compile")
