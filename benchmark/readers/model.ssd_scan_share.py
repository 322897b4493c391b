"""Share of the device's busy time in the traced sub-window spent in the
state-space scan itself: operations under ``GraniteHybrid/mamba/ssd``, a
part of ``model.ssm_share`` (``vftbench/scopes.py``)."""
from vftbench import scopes


def read(m):
    return scopes.share(m, "GraniteHybrid/mamba/ssd")
