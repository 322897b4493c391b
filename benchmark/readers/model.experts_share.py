"""Share of the device's busy time in the traced sub-window spent in the
routed expert layers: operations under the program's ``DeepSeekV2/moe`` scope
(gate, top-k, sort and gather, combine) and the two grouped products, which
XLA lowers ``jax.lax.ragged_dot`` to as ``custom-call`` operations under a
name of its own that keeps no scope: those are added where they name none,
so ``model.unscoped_share`` holds them too. The reckoning of
``model.moe_share``, whose file names granite's scope."""
from vftbench import scopes, xspace
from vftbench.measurement import MOSAIC_OPS


def read(m):
    scoped = scopes.under(m, "DeepSeekV2/moe")
    if scoped is None or not m.trace.get("busy_s"):
        return None
    kernels = scopes.named(m, MOSAIC_OPS, xspace.UNSCOPED) or 0.0
    return 100.0 * (scoped + kernels) / m.trace["busy_s"]
