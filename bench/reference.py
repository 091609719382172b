"""The plain reference's parts that every model kind shares: weights
made from the seed, the rounded conv and dense products, and the
numerics a configuration states.  It imports nothing of the program.
Each kind's weights, inputs and math are in `bench/models/<kind>.py`,
built from these.

Weights and inputs are made from the seed, on the device, in one
jitted call each; the program is handed the weights as arguments, and
the reference makes them again from the seed when it checks a run.

The reference is the configuration's math in `jax.numpy` and
`lax.conv_general_dilated` at `highest` matmul precision.  A
configuration states its numerics: `matmul_operands` "bfloat16" means
that every conv and dense product takes its operands rounded to
bfloat16 and accumulates in float32, which is what a float32 product at
the default precision does on a TPU's MXU.  The reference rounds the
same operands and computes the rest exactly, so a sound run differs
from it only by the order of float32 sums.  `dtype="bfloat16"` computes
everything in bfloat16 instead: that is the control, the step below the
stated precision.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

DN = ("NHWC", "HWIO", "NHWC")
HIGHEST = lax.Precision.HIGHEST


def _w(key, tag, shape, fan_in):
    return (1.0 / math.sqrt(fan_in)) * jax.random.truncated_normal(
        jax.random.fold_in(key, tag), -2.0, 2.0, shape, jnp.float32)


def _from_seed(make, seed: int):
    """Run `make(key)` as one jitted call on the device.  The key is an
    argument, not a constant, so every seed shares one compiled
    program."""
    return jax.jit(make)(jax.random.key(seed))


# -- the math ----------------------------------------------------------------

# (exponent bits, mantissa bits) of the narrower floating types
FORMATS = {"bfloat16": (8, 7)}


@dataclasses.dataclass(frozen=True)
class Numerics:
    """Where the computation rounds, all in float32 arrays.  `operands`:
    the type each product's operands are rounded to (a TPU product at
    the default precision rounds them to bfloat16).  `storage`: the
    type every stored result (a product's output, an activation, an
    updated weight) is rounded to.  Rounding is `lax.reduce_precision`,
    round to nearest even, which XLA never drops as excess precision."""
    operands: str = "float32"
    storage: str = "float32"

    @staticmethod
    def _rd(x, fmt):
        if fmt == "float32":
            return x
        e, m = FORMATS[fmt]
        return lax.reduce_precision(x, exponent_bits=e, mantissa_bits=m)

    def op(self, x):
        return self._rd(x, self.operands)

    def st(self, x):
        return self._rd(x, self.storage)


EXACT = Numerics()


def rounded(f, num: Numerics):
    """The bilinear product `f(a, b)` rounded as the numerics say, in
    both directions: the forward takes `a` and `b` rounded, and the
    gradients are the products of the rounded cotangent with the rounded
    other operand, each computed exactly (`f` runs at highest precision)
    and then stored."""
    @jax.custom_vjp
    def g(a, b):
        return num.st(f(num.op(a), num.op(b)))

    def fwd(a, b):
        ra, rb = num.op(a), num.op(b)
        return num.st(f(ra, rb)), (ra, rb)

    def bwd(res, ct):
        return tuple(num.st(d) for d in jax.vjp(f, *res)[1](num.op(ct)))

    g.defvjp(fwd, bwd)
    return g


def _conv_fn(stride, padding, dilation=1):
    return lambda x, w: lax.conv_general_dilated(
        x, w, window_strides=(stride, stride),
        padding=[(padding, padding)] * 2, rhs_dilation=(dilation, dilation),
        dimension_numbers=DN, precision=HIGHEST)


def _tconv_fn(stride, padding):
    """Transposed conv: the input gradient of the direct conv with
    filter `w` (K, K, Cin, Cout), applied to `x` (B, O, O, Cout)."""
    def f(x, w):
        b, o = x.shape[0], x.shape[1]
        n = stride * (o - 1) + w.shape[0] - 2 * padding
        fwd = lambda a: _conv_fn(stride, padding)(a, w)
        return jax.vjp(fwd, jnp.zeros((b, n, n, w.shape[2]), x.dtype))[1](
            x)[0]
    return f


def _dot(a, b):
    return jnp.dot(a, b, precision=HIGHEST)


def conv(x, w, stride, padding, dilation=1, *, num=EXACT):
    return rounded(_conv_fn(stride, padding, dilation), num)(x, w)


def tconv(x, w, stride, padding, *, num=EXACT):
    return rounded(_tconv_fn(stride, padding), num)(x, w)


def dot(a, b, *, num=EXACT):
    return rounded(_dot, num)(a, b)


def numerics(config: Dict, *, control: bool = False) -> Numerics:
    """The configuration's stated numerics, or the control's: the step
    below, every product's operands and every stored result in
    bfloat16."""
    if control:
        return Numerics("bfloat16", "bfloat16")
    return Numerics(config["numerics"]["matmul_operands"], "float32")
