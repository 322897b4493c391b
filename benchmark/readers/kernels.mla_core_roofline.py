"""The latent attention's core's share of its roofline: the least time the
chip could take for the scores and the mixing of one unit's causal,
same-document pairs (``costs/<config>.py``, ``kernels.mla_core``: the pairs
of the row the cell's ``inputs/`` draws, not ``T^2``) over the device time a
unit spends under ``DeepSeekV2/attn/core``. Prints which peak bounds."""
from vftbench import scopes


def read(m):
    kernel = (m.costs.get("kernels") or {}).get("mla_core")
    took = scopes.seconds_per_unit(
        m, scopes.under(m, "DeepSeekV2/attn/core"))
    if not kernel or not took:
        return None
    compute = kernel["flops"] / m.peaks["bf16_flops_per_s"]
    memory = kernel["bytes"] / m.peaks["hbm_bytes_per_s"]
    least = max(compute, memory)
    print(f"vftbench: kernels.mla_core_roofline: "
          f"{'compute' if compute >= memory else 'memory'}-bound, least "
          f"{least * 1e6:.2f} us, took {took * 1e6:.2f} us per unit")
    return 100.0 * least / took
