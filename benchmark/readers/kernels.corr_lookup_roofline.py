"""The fused correlation lookup's share of its roofline: the least time the
chip could take for the kernel's work on one unit (``costs/<config>.py``,
``kernels.corr_lookup``: lookup plus projection, all iterations) over the
kernel's device time per unit, which is its share of the device's busy time
times ``model.device_s_per_unit``. Prints which peak bounds."""
from vftbench.measurement import MOSAIC_OPS


def read(m):
    kernel = (m.costs.get("kernels") or {}).get("corr_lookup")
    share, per_unit = m.op_share(MOSAIC_OPS), m.device_s_per_unit()
    if not kernel or not share or not per_unit:
        return None
    compute = kernel["flops"] / m.peaks["bf16_flops_per_s"]
    memory = kernel["bytes"] / m.peaks["hbm_bytes_per_s"]
    least, took = max(compute, memory), share * per_unit
    print(f"vftbench: kernels.corr_lookup_roofline: "
          f"{'compute' if compute >= memory else 'memory'}-bound, least "
          f"{least * 1e6:.2f} us, took {took * 1e6:.2f} us per unit")
    return 100.0 * least / took
