"""Operations and bytes of convolutions and matrix products, from shapes.

The count is the algorithm's: one multiply-accumulate is two operations, and
every layer reads its input and writes its output once in the serving type.
It is not XLA's ``cost_analysis()``, which counts what the compiler emitted
(padding, recomputation and a scan body once) and changes with the compiler.
"""
from __future__ import annotations

from typing import Dict, Sequence


def out_len(n: int, kernel: int, stride: int, pad: int) -> int:
    return (n + 2 * pad - kernel) // stride + 1


class Tally:
    """Adds layers up: FLOPs, activation bytes and weight elements."""

    def __init__(self, act_bytes: int = 2) -> None:
        self.act_bytes = act_bytes
        self.flops = 0.0
        self.bytes = 0.0
        self.weights = 0.0
        self.layers: Dict[str, float] = {}

    def conv(self, name: str, in_positions: int, out_positions: int,
             taps: int, cin: int, cout: int, times: int = 1) -> None:
        """A convolution with ``taps`` kernel positions from ``cin`` to
        ``cout`` channels: ``2 * taps * cin * cout`` operations at each of
        ``out_positions`` outputs."""
        flops = 2.0 * out_positions * taps * cin * cout * times
        self.flops += flops
        self.bytes += (in_positions * cin + out_positions * cout) \
            * self.act_bytes * times
        self.weights += taps * cin * cout
        self.layers[name] = self.layers.get(name, 0.0) + flops

    def extra(self, name: str, flops: float, nbytes: float) -> None:
        self.flops += flops
        self.bytes += nbytes
        self.layers[name] = self.layers.get(name, 0.0) + flops

    def per_unit(self, batch: int, weight_bytes: int = 2) -> Dict[str, float]:
        """Totals for one unit: the weights are read once for a batch."""
        return {"flops": self.flops,
                "bytes": self.bytes + self.weights * weight_bytes / batch,
                "weight_elements": self.weights}


def prod(xs: Sequence[int]) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out
