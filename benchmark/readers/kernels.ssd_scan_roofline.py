"""The state-space scan's share of its roofline: the least time the chip
could take for the scan's work on one unit (``costs/<config>.py``,
``kernels.ssd_scan``) over the device time a unit spends under
``GraniteHybrid/mamba/ssd``. Prints which peak bounds."""
from vftbench import scopes


def read(m):
    kernel = (m.costs.get("kernels") or {}).get("ssd_scan")
    took = scopes.seconds_per_unit(
        m, scopes.under(m, "GraniteHybrid/mamba/ssd"))
    if not kernel or not took:
        return None
    compute = kernel["flops"] / m.peaks["bf16_flops_per_s"]
    memory = kernel["bytes"] / m.peaks["hbm_bytes_per_s"]
    least = max(compute, memory)
    print(f"vftbench: kernels.ssd_scan_roofline: "
          f"{'compute' if compute >= memory else 'memory'}-bound, least "
          f"{least * 1e6:.2f} us, took {took * 1e6:.2f} us per unit")
    return 100.0 * least / took
