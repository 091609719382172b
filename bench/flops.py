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
from typing import Dict, List, Sequence

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


def generator_ops(model: Dict, batch: int) -> List[Op]:
    """One generator forward at `batch` latents: the projection and the
    three 4x4 stride-2 transposed convs (4 -> 8 -> 16 -> 32)."""
    z, base, ch = model["z_dim"], model["base"], model["channels"]
    b = batch
    ops = [_dense("proj", b, z, 16 * 2 * base)]
    # (name, small side, Cin of the direct conv (upsampled side), Cout)
    for name, o, cin, cout in (("t1", 4, base, 2 * base),
                               ("t2", 8, base // 2, base),
                               ("t3", 16, ch, base // 2)):
        ops.append(_conv(name, b, (o, o), 4, cin, cout,
                         (b, o, o, cout), (b, 2 * o, 2 * o, cin), "pallas"))
    return ops


def aspp_ops(model: Dict, batch: int) -> List[Op]:
    """One ASPP head forward at `batch` feature maps: a 3x3 atrous branch
    per rate (same padding, full resolution) and the 1x1 classifier over
    their concatenation.  The classifier has no fused epilogue, so the
    program hands it to XLA."""
    h, w = model["feature_hw"]
    c, width, ncls = model["in_ch"], model["width"], model["n_classes"]
    rates = model["rates"]
    b = batch
    ops = [_conv(f"rate{r}", b, (h, w), 3, c, width, (b, h, w, c),
                 (b, h, w, width), "pallas") for r in rates]
    ops.append(_conv("classifier", b, (h, w), 1, width * len(rates), ncls,
                     (b, h, w, width * len(rates)), (b, h, w, ncls), "xla"))
    return ops


def discriminator_ops(model: Dict, batch: int) -> List[Op]:
    """One discriminator forward: three 4x4 stride-2 convs (32 -> 16 ->
    8 -> 4) and the dense head."""
    base, ch = model["base"], model["channels"]
    b = batch
    ops = []
    for name, o, cin, cout in (("c1", 16, ch, base // 2),
                               ("c2", 8, base // 2, base),
                               ("c3", 4, base, 2 * base)):
        ops.append(_conv(name, b, (o, o), 4, cin, cout,
                         (b, 2 * o, 2 * o, cin), (b, o, o, cout), "pallas"))
    ops.append(_dense("head", b, 16 * 2 * base, 1))
    return ops


# How many times one simultaneous GAN step needs each layer's product
# (a forward, an input gradient or a filter gradient each count once):
# the generator runs forward once (both losses share it), then its input
# and filter gradients; the discriminator runs forward on the fake and
# the real batch, its input gradients for the generator's loss, and its
# filter gradients plus the input gradients that reach them for its own
# loss on both batches (c1's input gradient is not needed there).
GAN_STEP_PASSES = {"proj": 2, "t1": 3, "t2": 3, "t3": 3,
                   "c1": 5, "c2": 7, "c3": 7, "head": 7}


def gan_step_ops(model: Dict, batch: int) -> List[Op]:
    """The useful work of one training step of the GAN at `batch`."""
    ops = generator_ops(model, batch) + discriminator_ops(model, batch)
    return [dataclasses.replace(o, flops=o.flops * GAN_STEP_PASSES[o.name],
                                bytes=o.bytes * GAN_STEP_PASSES[o.name])
            for o in ops]


SERVE_OPS = {"dcgan": generator_ops, "aspp": aspp_ops}


def serve_ops(model: Dict, batch: int) -> List[Op]:
    """The ops of one served launch of `model` at `batch` slots."""
    return SERVE_OPS[model["kind"]](model, batch)


def total_flops(ops: Sequence[Op]) -> float:
    return sum(o.flops for o in ops)


def roofline_s(ops: Sequence[Op], peaks: Dict) -> float:
    """Least time the chip could take for `ops`, each bounded by the
    larger of its FLOPs at peak rate and its bytes at HBM bandwidth."""
    return sum(max(o.flops / peaks["flops_per_s"],
                   o.bytes / peaks["hbm_bytes_per_s"]) for o in ops)
