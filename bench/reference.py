"""Plain reference of each configuration, and the weights and inputs a
run is made from.  It imports nothing of the program.

Weights and inputs are made here from the seed, on the device, in one
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
import numpy as np
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


# -- weights ---------------------------------------------------------------

def _generator(model: Dict, key) -> Dict:
    z, base, ch = model["z_dim"], model["base"], model["channels"]
    return {
        "proj": _w(key, 0, (z, 16 * 2 * base), z),
        "t1": _w(key, 1, (4, 4, base, 2 * base), 16 * base),
        "t2": _w(key, 2, (4, 4, base // 2, base), 8 * base),
        "t3": _w(key, 3, (4, 4, ch, base // 2), 16 * ch),
    }


def generator_params(model: Dict, seed: int) -> Dict:
    """DCGAN generator weights in the program's layout: `proj` maps the
    latent to a 4x4x(2*base) map, and each transposed-conv filter is
    stored as the (K, K, Cin, Cout) filter of the direct conv whose
    input gradient it is (Cin the upsampled side)."""
    return _from_seed(lambda key: _generator(model, key), seed)


def gan_params(model: Dict, seed: int) -> Dict:
    """The whole DCGAN training state, {"g": generator, "d":
    discriminator}: three 4x4 stride-2 convs (3 -> base/2 -> base ->
    2*base) and a dense head on the 4x4x(2*base) map."""
    base, ch = model["base"], model["channels"]

    def make(key):
        d = {"c1": _w(key, 11, (4, 4, ch, base // 2), 16 * ch),
             "c2": _w(key, 12, (4, 4, base // 2, base), 8 * base),
             "c3": _w(key, 13, (4, 4, base, 2 * base), 16 * base),
             "head": _w(key, 14, (16 * 2 * base, 1), 16 * 2 * base)}
        return {"g": _generator(model, key), "d": d}
    return _from_seed(make, seed)


def aspp_params(model: Dict, seed: int) -> Dict:
    """ASPP head weights in the program's layout: one 3x3 filter per
    rate (`rate<r>`) and the 1x1 classifier (`fuse`)."""
    c, width, ncls = model["in_ch"], model["width"], model["n_classes"]
    rates = model["rates"]

    def make(key):
        p = {f"rate{r}": _w(key, i, (3, 3, c, width), 9 * c)
             for i, r in enumerate(rates)}
        p["fuse"] = _w(key, 97, (1, 1, width * len(rates), ncls),
                       width * len(rates))
        return p
    return _from_seed(make, seed)


PARAMS = {"dcgan": generator_params, "aspp": aspp_params}


def serve_params(model: Dict, seed: int) -> Dict:
    return PARAMS[model["kind"]](model, seed)


# -- inputs ------------------------------------------------------------------

def payload_pool(model: Dict, seed: int, n: int) -> jax.Array:
    """`n` distinct request payloads made on the device from the seed:
    latents for the generator, post-ReLU feature maps (what a ResNet
    block 4 emits) for the ASPP head."""
    if model["kind"] == "dcgan":
        shape = (n, model["z_dim"])
        post = lambda x: x
    else:
        shape = (n, *model["feature_hw"], model["in_ch"])
        post = jax.nn.relu
    return _from_seed(lambda key: post(jax.random.normal(
        jax.random.fold_in(key, 1000), shape, jnp.float32)), seed)


def train_batch(model: Dict, seed: int, step: int) -> Dict[str, np.ndarray]:
    """The GAN's inputs at `step`, a function of (seed, step) alone:
    latents and "real" images in [-1, 1], made on the host as the
    trainer's feed is."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 5, step]))
    b, c = model["batch"], model["channels"]
    z = rng.standard_normal((b, model["z_dim"]), np.float32)
    real = np.tanh(rng.standard_normal((b, 32, 32, c), np.float32))
    return {"z": z, "real": real}


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


def generator_apply(params, z, *, num=EXACT):
    b = z.shape[0]
    act = lambda f, x: num.st(f(x))
    x = act(jax.nn.relu, dot(z, params["proj"], num=num).reshape(b, 4, 4, -1))
    x = act(jax.nn.relu, tconv(x, params["t1"], 2, 1, num=num))
    x = act(jax.nn.relu, tconv(x, params["t2"], 2, 1, num=num))
    return act(jnp.tanh, tconv(x, params["t3"], 2, 1, num=num))


def discriminator_apply(params, x, *, num=EXACT):
    for name in ("c1", "c2", "c3"):
        x = num.st(jax.nn.leaky_relu(conv(x, params[name], 2, 1, num=num),
                                     0.2))
    return dot(x.reshape(x.shape[0], -1), params["head"], num=num)


def aspp_apply(params, x, *, rates, num=EXACT):
    feats = [num.st(jax.nn.relu(conv(x, params[f"rate{r}"], 1, r, r,
                                     num=num))) for r in rates]
    return conv(jnp.concatenate(feats, axis=-1), params["fuse"], 1, 0,
                num=num)


def gan_step(state, z, real, lr, *, num=EXACT):
    """One simultaneous SGD step of the non-saturating GAN, each side's
    gradient taken against the other side's pre-step weights:
    (new_state, g_loss, d_loss, grads)."""
    sp = lambda x: num.st(jax.nn.softplus(x))

    def g_loss(g):
        fake = generator_apply(g, z, num=num)
        return sp(-discriminator_apply(state["d"], fake, num=num)).mean()

    def d_loss(d):
        fake = generator_apply(state["g"], z, num=num)
        return (sp(-discriminator_apply(d, real, num=num)).mean()
                + sp(discriminator_apply(d, fake, num=num)).mean())

    gl, gg = jax.value_and_grad(g_loss)(state["g"])
    dl, dg = jax.value_and_grad(d_loss)(state["d"])
    grads = {"g": gg, "d": dg}
    new = jax.tree.map(lambda p, g: num.st(p - lr * g), state, grads)
    return new, gl, dl, grads


def numerics(config: Dict, *, control: bool = False) -> Numerics:
    """The configuration's stated numerics, or the control's: the step
    below, every product's operands and every stored result in
    bfloat16."""
    if control:
        return Numerics("bfloat16", "bfloat16")
    return Numerics(config["numerics"]["matmul_operands"], "float32")


def serve_forward(model: Dict, num: Numerics):
    """`(params, batch) -> outputs` of one served launch."""
    if model["kind"] == "dcgan":
        return jax.jit(lambda p, b: generator_apply(p, b, num=num))
    rates = tuple(model["rates"])
    return jax.jit(lambda p, b: aspp_apply(p, b, rates=rates, num=num))
