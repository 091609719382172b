"""Useful operations and bytes of the conv work, counted from shapes.

"Useful" is the zero-free count of the repository's own accounting
(`repro.core.dataflow_sim.useful_macs`): every multiply of a real input
element by a real filter tap, B * Oh * Ow * Kh * Kw * Cin * Cout MACs for
a conv whose small (strided) side is Oh x Ow.  The zeros that a stride
or a dilation inserts are never counted, so the count is the same
whatever implements the conv; taps that fall on the border padding are
counted, as every implementation in the repository computes them.  A
transposed conv is counted as the direct conv whose input gradient it
is, and each gradient (input or filter) of a conv costs what its
forward costs.  One MAC is two FLOPs.

Bytes are the least traffic a launch needs: each operand read once and
the result written once, at 4 bytes an element (the arrays are float32
in HBM).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Sequence

F32 = 4


@dataclasses.dataclass(frozen=True)
class Op:
    """One conv or dense product: its name, useful FLOPs, least bytes,
    and the implementation the program launches it on (`pallas` or
    `xla`)."""
    name: str
    flops: float
    bytes: float
    kernel: str


def conv_macs(batch: int, out_hw: Sequence[int], k: int, cin: int,
              cout: int) -> int:
    """Useful MACs of a K x K conv mapping `cin` to `cout` channels whose
    small side is `out_hw`."""
    return batch * out_hw[0] * out_hw[1] * k * k * cin * cout


def _conv(name, batch, out_hw, k, cin, cout, x_shape, y_shape, kernel):
    n = lambda s: math.prod(s)
    return Op(name, 2.0 * conv_macs(batch, out_hw, k, cin, cout),
              float(F32 * (n(x_shape) + n(y_shape) + k * k * cin * cout)),
              kernel)


def _dense(name, m, k, n, kernel="xla"):
    return Op(name, 2.0 * m * k * n, float(F32 * (m * k + k * n + m * n)),
              kernel)


def total_flops(ops: Sequence[Op]) -> float:
    return sum(o.flops for o in ops)


def roofline_s(ops: Sequence[Op], peaks: Dict) -> float:
    """Least time the chip could take for `ops`, each bounded by the
    larger of its FLOPs at peak rate and its bytes at HBM bandwidth."""
    return sum(max(o.flops / peaks["flops_per_s"],
                   o.bytes / peaks["hbm_bytes_per_s"]) for o in ops)
