"""The least time the chip could take for one unit, over the time it took.

The least time is the larger of the algorithm's FLOPs over the peak FLOP/s
and its bytes over the peak bytes/s, both from ``costs/<config>.py`` (computed
from shapes, not from XLA's count of what it emitted) and ``peaks.json``. The
time it took is ``model.device_s_per_unit``. Prints which peak bounds."""


def read(m):
    took, least = m.device_s_per_unit(), m.roofline_s_per_unit()
    if not took or least is None:
        return None
    print(f"vftbench: model.forward_roofline: {least[1]}-bound, "
          f"{m.costs['flops'] / 1e9:.3f} GFLOP and "
          f"{m.costs['bytes'] / 1e6:.3f} MB per unit, least "
          f"{least[0] * 1e6:.2f} us, took {took * 1e6:.2f} us")
    return 100.0 * least[0] / took
