"""The held experts' grouped products' share of their roofline: the least
time the chip could take for them on one unit (``costs/<config>.py``,
``kernels.moe_experts``: both products of every layer at the expected
assignments a token) over their device time per unit: the kernels XLA lowers
``jax.lax.ragged_dot`` to show as ``custom-call`` operations, the only ones
of this program. Prints which peak bounds."""
from vftbench.measurement import MOSAIC_OPS


def read(m):
    kernel = (m.costs.get("kernels") or {}).get("moe_experts")
    share, per_unit = m.op_share(MOSAIC_OPS), m.device_s_per_unit()
    if not kernel or not share or not per_unit:
        return None
    compute = kernel["flops"] / m.peaks["bf16_flops_per_s"]
    memory = kernel["bytes"] / m.peaks["hbm_bytes_per_s"]
    least, took = max(compute, memory), share * per_unit
    print(f"vftbench: kernels.moe_experts_roofline: "
          f"{'compute' if compute >= memory else 'memory'}-bound, least "
          f"{least * 1e6:.2f} us, took {took * 1e6:.2f} us per unit")
    return 100.0 * least / took
